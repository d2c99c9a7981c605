"""The batched preemption search's time on every (S, K) pair of its
ladders, and the launch cost's one constant read off them.

    chiprun -- python3 scripts/search_kernel_times.py [--f 2] [--nl 8]

``plan_launches`` (kueue_tpu/ops/preemption_solver.py) costs a launch at
``K * (STEP_FLOOR_ROWS + S)``.  This times ``minimal_preemptions_batch``
at each pair (planes already on the device, the best of ``--reps``
calls ended by ``block_until_ready``), fits ``time = a * K * (c + S)``
by least squares of the relative error and prints ``c``: PERF.md §5 has
the table.  The kernel has no branch on its data, so empty planes time
as full ones.  Prints one JSON line a pair and one for the fit; refuses
to time a CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--f", type=int, default=2, help="flavor-resources")
    ap.add_argument("--nl", type=int, default=8, help="forest-local nodes")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from kueue_tpu.ops.preemption_kernel import minimal_preemptions_batch
    from kueue_tpu.ops.preemption_solver import K_LADDER, S_LADDER
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("no accelerator: a CPU timing is not a device time",
              file=sys.stderr)
        return 1
    NL, F = args.nl, args.f
    rows = []
    for S in S_LADDER:
        for K in K_LADDER:
            planes = jax.device_put((
                np.zeros((S, NL, F), np.int32),
                np.zeros((S, NL, F), np.int32),
                np.zeros((S, NL, F), np.int32),
                np.full((S, NL, F), 2**30, np.int32),
                np.zeros((S, NL, F), bool),
                np.full((S, NL), -1, np.int32),
                np.zeros(S, np.int32),
                np.ones((S, F), np.int32), np.ones((S, F), bool),
                np.zeros((S, K), np.int32),
                np.ones((S, K, F), np.int32),
                np.zeros((S, K), bool), np.zeros((S, K), bool),
                np.ones(S, bool), np.zeros(S, bool)))
            call = lambda: jax.block_until_ready(  # noqa: E731
                minimal_preemptions_batch(*planes, depth=args.depth))
            call()                                  # compile or load
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                call()
                best = min(best, time.perf_counter() - t0)
            rows.append((S, K, best))
            print(json.dumps({"S": S, "K": K, "ms": best * 1e3,
                              "us_per_step": best * 1e6 / (2 * K)}),
                  flush=True)
    # time = a*K*S + b*K, so c = b / a; each pair weighs the same
    # (relative error), or the largest would set the fit alone
    t = np.array([r[2] for r in rows])
    A = np.array([[K * S, K] for S, K, _ in rows], dtype=np.float64)
    (a, b), *_ = np.linalg.lstsq(A / t[:, None], np.ones(len(t)),
                                 rcond=None)
    print(json.dumps({"fit": "time = a*K*(c+S)", "a_us": a * 1e6,
                      "c_rows": b / a, "device": dev.device_kind,
                      "F": F, "NL": NL, "depth": args.depth}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
