"""The control of ``correct``, and the program's readings on many seeds.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 15 --out chiprun_out/control.jsonl

One process, several seeds (set-up is long and the compiled programs
are shared).  For each seed it runs the cell as ``run.py`` does, at the
cell's own size and load, for a short window, and prints the numbers
the comparison read for the program.  Then it puts each control in the
program's place: the plain reference of the configuration's deployment
kind with one stated guarantee switched off (the kind's ``CONTROLS``)
decides the same cycles from the same inputs, and the same comparison
reads it.  Every number compared
is exact, limit 0: the program has to read 0 on every seed and a
control above 0.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                         # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import sys                              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_readings(kind, plan, rounds, measured_from) -> dict:
    """Each control of the deployment kind replayed in the program's
    place: its decisions against the plain reference's, through the
    same comparison."""
    import copy

    import correct
    out = {}
    for broken in kind.CONTROLS:
        # the control's answers, shaped like the program's record
        ctl = kind.Reference(plan, broken=broken)
        answers = copy.deepcopy(rounds)
        for rnd in answers:
            ctl.begin_round(rnd)
            for cyc in rnd.cycles:
                res = ctl.cycle(cyc.clock)
                for name in kind.COMPARED:
                    setattr(cyc, name, getattr(res, name))
        verdict = correct.compare(kind, plan, answers, measured_from)
        out[broken] = {k: v["value"]
                       for k, v in verdict["compared"].items()}
        out[broken]["correct"] = verdict["correct"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="debugging only: relax the device gate")
    args = ap.parse_args(argv)
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    manifest = harness.load_manifest(ROOT)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        controls = {}
        result = harness.run_cell(
            manifest, args.workload, seed, args.seconds, False, t0,
            root=ROOT, require_tpu=not args.allow_cpu,
            on_rounds=lambda *a: controls.update(control_readings(*a)))
        row = {"workload": args.workload, "seed": seed,
               "correct": result["correct"],
               "program": {k: v["value"]
                           for k, v in result["compared"].items()},
               "controls": controls, "facts": result["facts"],
               "device": result["device"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    ok = all(r["correct"] and not any(c["correct"]
                                      for c in r["controls"].values())
             for r in rows)
    print(f"control: program correct on every seed and every control "
          f"not correct: {ok}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
