"""What it costs the device to take a window's changed cells into a
resident ``[C, M(, k)]`` row plane, in four forms.

    chiprun -- python3 scripts/resident_scatter_times.py [--window]

The one-chip launch keeps the fused window's row planes on the device
(``BurstSolver._resident_rows``) and sends the cells of each queue's
rows alone.  This times the update that puts them in place, at the
cells' shapes (C = 1,000, M = 65,536):

- ``cells``: an element scatter of n sorted, distinct ``(ci, mi)``;
- ``chunks``: ``[n, W(, k)]`` runs of W slots along M at ``(ci, start)``
  as one ``lax.scatter`` whose window is the run (the chip runs it as a
  loop over the runs, ~4.2 us each whatever W, a plane at a time);
- ``rows``: the sharded route's whole-queue rows, ``[D, M(, k)]``;
- ``runs`` (``--window``): the form the launch uses,
  ``BurstSolver._update_runs_fn``: one loop over the runs, every
  plane's run written in each step.

Each line gives the device's time for one donated plane, values already
on the device (``dev_ms``, the best of ``--reps`` calls ended by
``block_until_ready``), the same with the values coming from the host
(``with_h2d_ms``), and the program's compile or load time.  ``--window``
times every plane of the first cell's window in one program, as the
launch dispatches them, over Zipf-sized queues: the cells made up to
524,288, the runs that cover them as ``chunks`` and as ``runs``.
PERF.md §6 (PR 38) has the table.  Refuses to time a CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _best(call, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _sorted_cells(rng, C: int, M: int, n: int):
    flat = np.sort(rng.choice(C * M, size=n, replace=False))
    return (flat // M).astype(np.int32), (flat % M).astype(np.int32)


def _sorted_chunks(rng, C: int, M: int, n: int, W: int):
    n = min(n, C * (M // W))
    flat = np.sort(rng.choice(C * (M // W), size=n, replace=False))
    return np.stack([flat // (M // W), (flat % (M // W)) * W],
                    axis=1).astype(np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--c", type=int, default=1000)
    ap.add_argument("--m", type=int, default=65536)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--window", action="store_true",
                    help="also the first cell's planes in one program")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from jax import lax
    from kueue_tpu.ops.burst import BurstSolver, _row_runs
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("no accelerator: a CPU timing is not a device time",
              file=sys.stderr)
        return 1
    C, M, reps = args.c, args.m, args.reps
    rng = np.random.default_rng(38)

    def shape(k):
        return (C, M) if k == 0 else (C, M, k)

    def timed(form, k, dtype, n, W, fn, idx, vals):
        """One line: ``fn(plane, idx, vals)`` donates its plane."""
        jit = jax.jit(fn, donate_argnums=0)
        plane = jax.device_put(np.zeros(shape(k), dtype), dev)
        d_idx, d_vals = jax.device_put((idx, vals), dev)
        t0 = time.perf_counter()
        plane = jax.block_until_ready(jit(plane, d_idx, d_vals))
        build_s = time.perf_counter() - t0
        box = [plane]

        def on_device():
            box[0] = jax.block_until_ready(jit(box[0], d_idx, d_vals))

        def from_host():
            box[0] = jax.block_until_ready(jit(box[0], idx, vals))

        line = {"form": form, "k": k, "dtype": np.dtype(dtype).name,
                "n": n, "W": W, "cells": n * max(W, 1),
                "mb": vals.nbytes / 1e6,
                "dev_ms": _best(on_device, reps),
                "with_h2d_ms": _best(from_host, reps),
                "build_s": build_s}
        print(json.dumps(line), flush=True)
        del box[0]

    def scatter_chunks(plane, at, vals):
        dnums = lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, vals.ndim)),
            inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0, 1))
        return lax.scatter(plane, at, vals, dnums, indices_are_sorted=True,
                           unique_indices=True,
                           mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    def cells_fn(plane, idx, vals):
        return plane.at[idx].set(vals, indices_are_sorted=True,
                                 unique_indices=True)

    def rows_fn(plane, idx, vals):
        return plane.at[idx].set(vals, indices_are_sorted=True,
                                 unique_indices=True)

    def vals_of(lead, k, dtype):
        full = lead if k == 0 else lead + (k,)
        return rng.integers(0, 2, size=full).astype(dtype)

    # (a) the element scatter: how it grows with n, with k, with a byte
    for k, dtype, n in ([(0, np.int32, n) for n in
                         (50_000, 100_000, 200_000, 400_000)]
                        + [(k, np.int32, 200_000) for k in (1, 2, 4, 8)]
                        + [(0, np.bool_, 200_000), (2, np.bool_, 200_000)]):
        ci, mi = _sorted_cells(rng, C, M, n)
        timed("cells", k, dtype, n, 0, cells_fn, (ci, mi),
              vals_of((n,), k, dtype))
    # (b) runs of W along M: the run's length at one byte count, then n,
    # k and a byte at W = 256
    for k, dtype, n, W in ([(0, np.int32, 512 * 1024 // W, W) for W in
                            (128, 256, 1024)]
                           + [(0, np.int32, n, 256) for n in
                              (512, 4096, 16384)]
                           + [(k, np.int32, 2048, 256) for k in (1, 2, 4, 8)]
                           + [(k, np.bool_, 2048, 256) for k in (0, 2, 8)]):
        at = _sorted_chunks(rng, C, M, n, W)
        timed("chunks", k, dtype, len(at), W, scatter_chunks, at,
              vals_of((len(at), W), k, dtype))
    # (c) whole rows, the sharded route's unit
    for k, dtype, D in ((0, np.int32, 64), (0, np.int32, 256),
                        (2, np.int32, 256)):
        D = min(D, C)
        rows = np.sort(rng.choice(C, size=D, replace=False)).astype(np.int32)
        timed("rows", k, dtype, D, M, rows_fn, rows,
              vals_of((D, M), k, dtype))

    if args.window:
        # the first cell's mirrored planes (R = 2, F = 2, G = 1) in one
        # program, as the launch runs it
        planes = {"wl_req": (2, np.int32), "wl_rank": (0, np.int32),
                  "wl_cycle_rank": (0, np.int32), "wl_prio": (0, np.int32),
                  "wl_uidrank": (0, np.int32), "vec_ok": (0, np.bool_),
                  "elig0": (0, np.bool_), "parked0": (0, np.bool_),
                  "resume0": (1, np.int32), "adm0": (0, np.bool_),
                  "adm_seq0": (0, np.int32), "adm_usage0": (2, np.int32),
                  "adm_uses0": (2, np.bool_)}
        # queues of 288,000 Zipf(1.0) rows and 34 more each
        share = 1.0 / np.arange(1, C + 1)
        extent = (288_000 * share / share.sum()).astype(np.int64) + 34
        ci = np.repeat(np.arange(C, dtype=np.int32), extent)
        mi = (np.arange(len(ci), dtype=np.int32) - np.repeat(
            (np.cumsum(extent) - extent).astype(np.int32), extent))
        update_runs = BurstSolver()._update_runs_fn()

        def fused(fn):
            return jax.jit(lambda ps, idx, vs: tuple(
                fn(p, idx, v) for p, v in zip(ps, vs)), donate_argnums=0)

        forms = [("cells", 524_288, 0)]
        forms += [(form, rung, W) for W, rung in ((1024, 2048), (256, 4096))
                  for form in ("chunks", "runs")]
        for form, n, W in forms:
            if form == "cells":
                # the queues' cells, then the slots that follow in the
                # last queue: sorted, each once
                more = n - len(ci)
                idx = (np.concatenate((ci, np.full(more, C - 1, np.int32))),
                       np.concatenate((mi, mi[-1] + 1 + np.arange(
                           more, dtype=np.int32))))
                count, lead, call = n, (n,), fused(cells_fn)
            else:
                idx, count = _row_runs(extent, W, n)
                lead = (n, W)
                if form == "chunks":
                    # lax.scatter takes each run once: the real ones
                    idx, lead = idx[:count], (count, W)
                    call = fused(scatter_chunks)
                else:
                    call = (lambda ps, at, vs, count=count: update_runs(
                        ps, at, np.int32(count), vs))
            vals = tuple(vals_of(lead, k, dt) for k, dt in planes.values())
            box = [tuple(jax.device_put(np.zeros(shape(k), dt), dev)
                         for k, dt in planes.values())]
            d_idx, d_vals = jax.device_put((idx, vals), dev)
            t0 = time.perf_counter()
            box[0] = jax.block_until_ready(call(box[0], d_idx, d_vals))
            build_s = time.perf_counter() - t0

            def on_device():
                box[0] = jax.block_until_ready(call(box[0], d_idx, d_vals))

            def from_host():
                box[0] = jax.block_until_ready(call(box[0], idx, vals))

            print(json.dumps({
                "form": "window." + form, "planes": len(planes),
                "n": count, "W": W, "cells": count * max(W, 1),
                "mb": sum(v.nbytes for v in vals) / 1e6,
                "dev_ms": _best(on_device, reps),
                "with_h2d_ms": _best(from_host, reps),
                "build_s": build_s}), flush=True)
            del box[0]
    print(json.dumps({"device": dev.device_kind, "C": C, "M": M}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
