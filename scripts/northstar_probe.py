"""North-star scale probe (BASELINE.json): classify 100k pending
workloads against 1k ClusterQueues in one device cycle, and run the
sequential admit scan over the 1k cycle heads.

Run on TPU: ``python scripts/northstar_probe.py [W] [C]``.
Prints phase timings; the target is <1 s p99 per cycle on v5e.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax  # noqa: E402

from kueue_tpu.ops.cycle import solve_cycle, solve_cycle_forests  # noqa: E402


def synth(W=100_000, C=1_000, S=4, R=3, cohorts=64, seed=0):
    """A synthetic packed cycle at north-star scale (no host objects —
    this probes the device plane, not the packer)."""
    rng = np.random.default_rng(seed)
    N = C + cohorts
    parent = np.full(N, -1, dtype=np.int32)
    parent[:C] = C + rng.integers(0, cohorts, C)      # CQ → cohort
    F = S * R
    nominal = rng.integers(8, 64, (C, F)).astype(np.int32) * 1000
    subtree = np.zeros((N, F), dtype=np.int32)
    subtree[:C] = nominal
    for c in range(C):                                # cohort subtree sums
        subtree[parent[c]] += nominal[c]
    guaranteed = subtree.copy()
    usage0 = (nominal * rng.random((C, F)) * 0.8).astype(np.int32)
    usage0 = np.concatenate([usage0, np.zeros((cohorts, F), np.int32)])
    for c in range(C):
        usage0[parent[c]] += usage0[c]
    borrow_cap = np.full((N, F), 2**31 // 64, dtype=np.int32)
    has_blim = np.zeros((N, F), dtype=bool)
    slot_fr = np.zeros((C, S, R), dtype=np.int32)
    for s in range(S):
        for r in range(R):
            slot_fr[:, s, r] = s * R + r
    slot_valid = np.ones((C, 1, S), dtype=bool)
    can_preempt = np.zeros(C, dtype=bool)
    wl_cq = rng.integers(0, C, W).astype(np.int32)
    wl_requests = rng.integers(1, 16, (W, R)).astype(np.int32) * 500
    wl_priority = rng.integers(0, 100, W).astype(np.int32)
    wl_timestamp = rng.random(W).astype(np.float64)
    depth = 2      # chain node count: CQ -> cohort
    return (usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
            nominal, slot_fr, slot_valid, can_preempt,
            wl_cq, wl_requests, wl_priority, wl_timestamp), depth


def bench_fn(fn, *args, reps=20, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], times[-1], out


def main():
    W = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    C = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000
    print(f"devices: {jax.devices()}")
    args, depth = synth(W=W, C=C)
    print(f"W={W} C={C} — compiling…")

    p50, worst, out = bench_fn(solve_cycle, *args, depth=depth,
                               run_scan=False)
    fit = int(np.asarray(out[4] >= 0).sum())
    print(f"phase-1 classify {W}x{C}: p50={p50 * 1e3:.1f}ms "
          f"worst={worst * 1e3:.1f}ms  ({fit} fits)")

    # the sequential admit scan runs over cycle heads (one per CQ)
    heads_args, _ = synth(W=C, C=C, seed=1)
    p50s, worsts, _ = bench_fn(solve_cycle, *heads_args, depth=depth,
                               run_scan=True)
    print(f"flat {C}-head admit scan: p50={p50s * 1e3:.1f}ms "
          f"worst={worsts * 1e3:.1f}ms")

    # forest-parallel scan: cohort forests admit in lockstep
    cohorts = 64
    forest_of_node = np.concatenate([
        np.asarray(heads_args[5][:C]) - C,     # CQ → its cohort index
        np.arange(cohorts, dtype=np.int32)])   # cohorts are the roots
    max_group = int(np.bincount(
        forest_of_node[np.maximum(np.asarray(heads_args[10]), 0)],
        minlength=cohorts).max())
    p50f, worstf, _ = bench_fn(
        solve_cycle_forests, *heads_args,
        forest_of_node.astype(np.int32), depth=depth,
        n_forests=cohorts, max_forest_wl=max_group + 1)
    print(f"forest-parallel admit scan ({cohorts} forests, "
          f"{max_group + 1} steps): p50={p50f * 1e3:.1f}ms "
          f"worst={worstf * 1e3:.1f}ms")
    total = p50 + p50f
    print(f"north-star cycle (classify backlog + admit heads): "
          f"{total * 1e3:.1f}ms  (target <1000ms)")


if __name__ == "__main__":
    main()
