"""Several runs of one cell in one call, as the driver's check makes them.

    python3 benchmarks/measure.py --workload <cell> --seeds 1,2,3 \
        [--trace 0|1] [--seconds N] [--sets 2] [--out chiprun_out/x.jsonl]

Each run is a new process of ``BENCHMARK.json``'s command (this parent
never touches JAX, so the chip is free for each child).  The last line
of each run is linted against the contract and kept; at the end each
end-to-end metric's median and spread (the distance between the first
and third quartile as a share of the median,
``statistics.quantiles(values, n=4)``) is printed a set.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import lint_manifest  # noqa: E402


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = 0
    sets = []
    for set_no in range(args.sets):
        rows = []
        for seed in seeds:
            cmd = manifest["command"] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(args.trace)]
            t0 = time.time()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.time() - t0
            lines = [x for x in p.stdout.splitlines() if x.strip()]
            last = lines[-1] if lines else ""
            found = (lint_manifest.lint_line(manifest, args.workload,
                                             args.trace, last)
                     if p.returncode == 0 else
                     [f"exit code {p.returncode}"])
            faults += len(found)
            print(f"== set {set_no} seed {seed} trace {args.trace} "
                  f"rc {p.returncode} wall {wall:.0f}s faults {found}",
                  flush=True)
            print(p.stderr[-3000:], flush=True)
            row = {"set": set_no, "seed": seed, "trace": args.trace,
                   "rc": p.returncode, "wall_s": wall, "faults": found}
            try:
                row["result"] = json.loads(last)
                print(json.dumps(row["result"]["metrics"]), flush=True)
            except ValueError:
                row["stdout_tail"] = p.stdout[-2000:]
            rows.append(row)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
        sets.append(rows)
    if not args.trace:
        for set_no, rows in enumerate(sets):
            good = [r["result"] for r in rows if "result" in r]
            for m in manifest["end_to_end"]:
                vals = [g["metrics"][m["name"]]["value"] for g in good
                        if m["name"] in g["metrics"]]
                if len(vals) >= 2:
                    print(f"set {set_no} {m['name']}: median "
                          f"{statistics.median(vals):.4f} spread "
                          f"{100 * spread(vals):.2f}% of {len(vals)} "
                          f"{[round(v, 3) for v in vals]}")
            print(f"set {set_no} correct: "
                  f"{[g['correct'] for g in good]}")
    print(f"measure: {faults} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
