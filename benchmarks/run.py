"""The benchmark's entry point.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints the result as the last line of standard output.  Finds a TPU or
fails: there is no CPU path.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # before any heavy import

import argparse                         # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import sys                              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program and the benchmark's own modules, from this checkout
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    manifest = harness.load_manifest(ROOT)
    result = harness.run_cell(manifest, args.workload, args.seed,
                              args.seconds, bool(args.trace), T_START,
                              root=ROOT)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
