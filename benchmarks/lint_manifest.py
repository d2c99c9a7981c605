"""Lint ``BENCHMARK.json``, the benchmark's data files and a result line
against the benchmark's contract, before any chip time is spent.

    python3 benchmarks/lint_manifest.py                      # the manifest
    python3 benchmarks/lint_manifest.py --line <cell> <0|1>  # + stdin's last line

Prints one fault a line and exits 1 if there is any.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP = ["command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"]
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_size", "head_dim", "expansion", "experts_per_tok")


def _text(s, lo=1, hi=200) -> bool:
    return (isinstance(s, str) and lo <= len(s) <= hi and s.isprintable()
            and "\t" not in s and "\n" not in s)


def _keys(entry, required, optional, what, faults):
    if not isinstance(entry, dict):
        faults.append(f"{what}: not an object")
        return False
    extra = set(entry) - set(required) - set(optional)
    missing = set(required) - set(entry)
    if extra:
        faults.append(f"{what}: keys not in the contract {sorted(extra)}")
    if missing:
        faults.append(f"{what}: missing keys {sorted(missing)}")
    return not missing


def _under(path: str, paths) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/")
               for p in paths)


def lint(manifest: dict, root: str = ROOT, raw_size: int | None = None
         ) -> list[str]:
    f: list[str] = []
    if raw_size is not None and raw_size > 64 * 1024:
        f.append(f"BENCHMARK.json is {raw_size} bytes, over 64 KiB")
    if sorted(manifest) != sorted(TOP):
        f.append(f"top-level keys {sorted(manifest)} are not exactly "
                 f"{sorted(TOP)}")
        return f

    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        f.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if not (isinstance(p, str) and PATH.match(p)) or p.startswith("/") \
                or ".." in p.split("/"):
            f.append(f"paths: {p!r} is not a plain relative path")
        elif not os.path.isdir(os.path.join(root, p)):
            f.append(f"paths: {p!r} is not a directory")

    cmd = manifest["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_text(w) for w in cmd)):
        f.append("command: a list of 1 to 32 strings of 1 to 200 "
                 "characters")
    else:
        for w in cmd:
            if w.startswith("/") or ".." in w.split("/"):
                f.append(f"command: {w!r} leaves the repo")
            elif os.path.exists(os.path.join(root, w)) \
                    and not _under(w, paths):
                f.append(f"command: {w!r} is a file outside paths")

    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= 51):
        f.append(f"run_seconds: {rs!r} is not a whole number from 1 to 51")

    names_seen: dict[str, set] = {"config": set(), "workload": set(),
                                  "metric": set()}

    def name_ok(n, what, group):
        if not (isinstance(n, str) and NAME.match(n)):
            f.append(f"{what}: name {n!r} does not fit the contract")
            return
        if n in names_seen[group]:
            f.append(f"{what}: name {n!r} appears twice")
        names_seen[group].add(n)

    # configs
    configs = manifest["configs"]
    if not (isinstance(configs, list) and 1 <= len(configs) <= 24):
        f.append("configs: 1 to 24 entries")
        configs = []
    files = set()
    for c in configs:
        what = f"config {c.get('name') if isinstance(c, dict) else c!r}"
        if not _keys(c, ["name", "source", "file", "reduced", "why"], [],
                     what, f):
            continue
        name_ok(c["name"], what, "config")
        if not _text(c["source"]):
            f.append(f"{what}: source must be 1 to 200 printable "
                     f"characters on one line, not "
                     f"{len(str(c['source']))}")
        if not _text(c["why"]):
            f.append(f"{what}: why must be 1 to 200 characters on one line")
        red = c["reduced"]
        if not (isinstance(red, list) and len(red) <= 16
                and all(isinstance(k, str) and NAME.match(k) for k in red)):
            f.append(f"{what}: reduced is at most 16 names")
        else:
            for k in red:
                if k.endswith(("_dim", "_rank")) or any(
                        w in k for w in WIDTH_WORDS):
                    f.append(f"{what}: reduced names a width, {k!r}")
        path = c["file"]
        if not (isinstance(path, str) and PATH.match(path)
                and _under(path, paths)):
            f.append(f"{what}: file {path!r} is not under paths")
        elif path in files:
            f.append(f"{what}: file {path!r} is another configuration's")
        elif not os.path.isfile(os.path.join(root, path)):
            f.append(f"{what}: file {path!r} does not exist")
        else:
            files.add(path)
            try:
                with open(os.path.join(root, path)) as fh:
                    body = json.load(fh)
            except ValueError as e:
                f.append(f"{what}: file {path!r} is not JSON: {e}")
            else:
                if body.get("source") != c["source"]:
                    f.append(f"{what}: the file's source differs from "
                             f"the manifest's")
                if body.get("reduced") != c["reduced"]:
                    f.append(f"{what}: the file's reduced differs from "
                             f"the manifest's")
                for key in ("assumed", "deployment", "guarantees"):
                    if not body.get(key):
                        f.append(f"{what}: the file states no {key}")
                kind = body.get("kind")
                if not (isinstance(kind, str) and kind.isidentifier()):
                    f.append(f"{what}: the file names no deployment kind")
                elif not any(os.path.isfile(os.path.join(
                        root, p, "deployment_kinds", kind, "__init__.py"))
                        for p in paths):
                    f.append(f"{what}: deployment kind {kind!r} has no "
                             f"package deployment_kinds/{kind}/")

    # workloads
    cells = manifest["workloads"]
    if not (isinstance(cells, list) and 1 <= len(cells) <= 24):
        f.append("workloads: 1 to 24 cells")
        cells = []
    pairs = set()
    used = set()
    four = 0
    for w in cells:
        what = f"workload {w.get('name') if isinstance(w, dict) else w!r}"
        if not _keys(w, ["name", "config", "traffic", "chips", "why"], [],
                     what, f):
            continue
        name_ok(w["name"], what, "workload")
        if w["config"] not in names_seen["config"]:
            f.append(f"{what}: config {w['config']!r} is not defined")
        used.add(w["config"])
        if not (isinstance(w["traffic"], str) and NAME.match(w["traffic"])):
            f.append(f"{what}: traffic {w['traffic']!r} is not a name")
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            f.append(f"{what}: chips is 1 or 4")
        four += w["chips"] == 4
        if not _text(w["why"]):
            f.append(f"{what}: why must be 1 to 200 characters on one "
                     f"line, not {len(str(w['why']))}")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            f.append(f"{what}: the pair {pair} appears twice")
        pairs.add(pair)
        # the traffic's data file and its kind
        tfile = None
        for p in paths:
            for suffix in DATA_SUFFIXES:
                cand = os.path.join(root, p, "traffic",
                                    str(w["traffic"]) + suffix)
                if os.path.isfile(cand):
                    tfile = cand
        if tfile is None:
            f.append(f"{what}: no data file traffic/{w['traffic']}.*")
        elif tfile.endswith(".json"):
            with open(tfile) as fh:
                kind = json.load(fh).get("kind")
            if not any(os.path.isfile(os.path.join(
                    root, p, "traffic_kinds", f"{kind}.py")) for p in paths):
                f.append(f"{what}: traffic kind {kind!r} has no generator")
    if four > max(1, len(cells) // 4):
        f.append(f"workloads: {four} cells ask for 4 chips, over a quarter")
    for c in names_seen["config"] - used:
        f.append(f"config {c}: no cell uses it")

    # metrics
    cell_names = names_seen["workload"]
    e2e = manifest["end_to_end"]
    if not (isinstance(e2e, list) and 1 <= len(e2e) <= 16):
        f.append("end_to_end: 1 to 16 metrics")
        e2e = []
    e2e_cells: dict[str, set] = {}
    for m in e2e:
        what = f"end_to_end {m.get('name') if isinstance(m, dict) else m!r}"
        if not _keys(m, ["name", "unit", "better", "bound", "source"],
                     ["workloads"], what, f):
            continue
        name_ok(m["name"], what, "metric")
        _metric_common(m, what, cell_names, f)
        if m["source"] not in ("host_clock", "device_trace"):
            f.append(f"{what}: an end-to-end metric takes host_clock or "
                     f"device_trace")
        b = m["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.1):
            f.append(f"{what}: bound {b!r} is not between 0.01 and 0.1")
        e2e_cells[m["name"]] = set(m.get("workloads", cell_names))
    if "setup_s" not in e2e_cells:
        f.append("end_to_end: setup_s is missing")

    per = manifest["per_layer"]
    if not (isinstance(per, list) and 1 <= len(per) <= 128):
        f.append("per_layer: 1 to 128 metrics")
        per = []
    layer_cells = set()
    for m in per:
        what = f"per_layer {m.get('name') if isinstance(m, dict) else m!r}"
        if not _keys(m, ["name", "unit", "better", "source", "layer",
                         "moves"], ["workloads"], what, f):
            continue
        name_ok(m["name"], what, "metric")
        _metric_common(m, what, cell_names, f)
        if m["source"] not in SOURCES:
            f.append(f"{what}: source {m['source']!r}")
        if not _text(m["layer"]):
            f.append(f"{what}: layer must be 1 to 200 characters")
        if m["moves"] not in e2e_cells:
            f.append(f"{what}: moves {m['moves']!r} is no end-to-end "
                     f"metric")
        else:
            in_cells = set(m.get("workloads", e2e_cells[m["moves"]]))
            for c in in_cells - e2e_cells[m["moves"]]:
                f.append(f"{what}: cell {c} does not report "
                         f"{m['moves']}")
            layer_cells |= in_cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            if m["unit"] != "%":
                f.append(f"{what}: a roofline or mfu share has the unit %")
        spec = None
        for p in paths:
            cand = os.path.join(root, p, "metrics", f"{m['name']}.json")
            if os.path.isfile(cand):
                with open(cand) as fh:
                    spec = json.load(fh)
                if not os.path.isfile(os.path.join(
                        root, p, "metric_kinds", f"{spec.get('kind')}.py")):
                    f.append(f"{what}: reader kind {spec.get('kind')!r} "
                             f"does not exist")
        if spec is None:
            f.append(f"{what}: no reader file metrics/{m['name']}.json")
    for c in cell_names:
        if c not in e2e_cells.get("setup_s", set()):
            f.append(f"workload {c}: does not report setup_s")
        if not any(c in cs for n, cs in e2e_cells.items()
                   if n != "setup_s"):
            f.append(f"workload {c}: reports no end-to-end metric "
                     f"beside setup_s")
        if c not in layer_cells:
            f.append(f"workload {c}: reports no per-layer metric")

    # files under paths are named from the characters of a name and /
    for p in paths:
        for d, dirs, fs in os.walk(os.path.join(root, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for x in fs:
                rel = os.path.relpath(os.path.join(d, x), root)
                if not PATH.match(rel) and not x.endswith(".pyc"):
                    f.append(f"file {rel!r}: characters outside a name's")
    return f


def _metric_common(m, what, cell_names, f):
    if not (isinstance(m["unit"], str) and UNIT.match(m["unit"])):
        f.append(f"{what}: unit {m['unit']!r} is not 1 to 16 of "
                 f"letters, digits and _/%.-")
    if m["better"] not in ("lower", "higher"):
        f.append(f"{what}: better is lower or higher")
    if "workloads" in m:
        ws = m["workloads"]
        if not (isinstance(ws, list) and ws
                and all(w in cell_names for w in ws)):
            f.append(f"{what}: workloads must list defined cells")


def lint_line(manifest: dict, cell: str, trace: int, line: str
              ) -> list[str]:
    """The last line of a run's standard output against the contract."""
    f: list[str] = []
    try:
        r = json.loads(line)
    except ValueError as e:
        return [f"result line is not JSON: {e}"]
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        if k not in r:
            f.append(f"result: key {k!r} is missing")
    if f:
        return f
    if not isinstance(r["correct"], bool):
        f.append("result: correct is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(r[k], int) or isinstance(r[k], bool) or r[k] < 0:
            f.append(f"result: {k} is not a count")
    group = "per_layer" if trace else "end_to_end"
    cells = [w["name"] for w in manifest["workloads"]]
    expected = {}
    for m in manifest[group]:
        if cell in m.get("workloads", cells):
            expected[m["name"]] = m["unit"]
    got = r["metrics"]
    for name in set(got) - set(expected):
        f.append(f"result: metric {name!r} is not one of the cell's "
                 f"{group} metrics")
    for name, unit in expected.items():
        if name not in got:
            f.append(f"result: metric {name!r} is missing")
            continue
        v = got[name]
        if not (isinstance(v, dict) and isinstance(v.get("value"),
                                                   (int, float))
                and not isinstance(v.get("value"), bool)):
            f.append(f"result: metric {name!r} has no numeric value")
        elif v.get("unit") != unit:
            f.append(f"result: metric {name!r} has unit {v.get('unit')!r},"
                     f" the manifest says {unit!r}")
        elif not trace and v["value"] == 0:
            f.append(f"result: end-to-end metric {name!r} is 0")
        elif (name.endswith("_roofline") or "mfu" in name) and not (
                0 < v["value"] <= 105):
            f.append(f"result: {name!r} reads {v['value']}, outside "
                     f"0 to 105")
    dev = r["device"]
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in dev:
            f.append(f"result: device.{k} is missing")
    if trace:
        for k in ("busy_s", "window_s"):
            if not (isinstance(dev.get(k), (int, float)) and dev[k] > 0):
                f.append(f"result: device.{k} must be above 0 in a "
                         f"traced run")
        bd = r.get("breakdown")
        if bd is not None:
            for k in ("device_ops", "idle_gaps"):
                rows = bd.get(k)
                if not (isinstance(rows, list) and len(rows) <= 10 and all(
                        isinstance(x, list) and len(x) == 2 for x in rows)):
                    f.append(f"result: breakdown.{k} is not at most 10 "
                             f"[name, seconds] pairs")
    if "compared" not in r:
        f.append("result: the compared numbers are missing")
    elif list(r)[-1] != "compared":
        f.append("result: compared is not the last key")
    else:
        for name, row in r["compared"].items():
            if not (isinstance(row, dict) and "value" in row
                    and "limit" in row):
                f.append(f"result: compared.{name} lacks value or limit")
    return f


def main(argv) -> int:
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as fh:
        raw = fh.read()
    manifest = json.loads(raw)
    faults = lint(manifest, ROOT, raw_size=len(raw.encode()))
    if len(argv) >= 3 and argv[0] == "--line":
        lines = [x for x in sys.stdin.read().splitlines() if x.strip()]
        faults += (lint_line(manifest, argv[1], int(argv[2]), lines[-1])
                   if lines else ["no result line on standard input"])
    for x in faults:
        print(x)
    print(f"lint_manifest: {len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
