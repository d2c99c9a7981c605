"""Peaks of the chips the benchmark runs on, and the bytes a kernel needs.

Peaks: Google Cloud documentation, "TPU v5e" system architecture: one
chip has 16 GB of HBM at 819 GB/s and 197 TFLOP/s in bf16.  A device
kind that is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return PEAKS[device_kind]


# What one launch of the fused window has to read, and what any program
# has to read to decide one cycle, counted from the cluster and never
# from the implementation.  A real row is a workload
# the window can decide about: every admitted workload of a forest that
# can preempt, and the pending workloads a queue can reach inside one
# window.  Deciding needs, a row: its request in each resource, its
# priority, its place in queue order, its reservation order and its uid
# order (the three tie-breaks of Kueue's candidate ordering), each a
# 32-bit word, and one byte of state (pending, parked, admitted).  A
# queue adds, a resource, its nominal quota, borrowing limit and usage.
# Grid slots that hold no workload, and wider or narrower planes the
# program happens to use, are not counted: padding is the program's
# cost, not the problem's.
WORD = 4


def row_bytes(n_resources: int) -> int:
    return WORD * n_resources + 4 * WORD + 1


def queue_bytes(n_resources: int) -> int:
    return 3 * WORD * n_resources


def burst_launch_bytes(real_rows: int, queues: int, n_resources: int) -> int:
    """Least bytes one fused-window launch, or one decided cycle by any
    program, moves through HBM: every real row and every queue's quota
    state read once."""
    return (real_rows * row_bytes(n_resources)
            + queues * queue_bytes(n_resources))
