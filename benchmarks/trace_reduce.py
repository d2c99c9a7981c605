"""From a profiler trace (.xplane.pb) to busy time, kernel time and gaps.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event an operation that ran, ``XLA Modules`` one event a
program (``jit_<name>(<hash>)``).  Busy time is the union of the
operations' intervals, averaged over the device planes; idle is the
window less busy.  Gaps between busy intervals are attributed to host
marks, (name, start, end) on the trace's own clock, by the narrowest
mark that covers the middle of the gap.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench.window"


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def _union(intervals):
    """Merged, sorted (start, end) list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def program_name(event_name: str) -> str:
    """``jit__burst_cycles(123)`` -> ``jit__burst_cycles``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def load_trace(path: str) -> dict | None:
    """Device events and host ``bench.*`` annotations of one trace file.
    None when the trace holds no device plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host_marks = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events]
                    if line.name == OPS_LINE:
                        ops = evs
                    else:
                        modules = evs
            devices.append({"ops": ops, "modules": modules})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_marks.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    if not devices:
        return None
    return {"devices": devices, "host_marks": host_marks}


def window_of(loaded: dict):
    """(start_ns, end_ns) of the ``bench.window`` annotation, or None."""
    w = [m for m in loaded["host_marks"] if m[0] == WINDOW_MARK]
    return (w[0][1], w[0][2]) if w else None


def onto_trace_clock(window, t_mark: float, marks):
    """Host marks (name, start, end) in seconds of ``time.perf_counter``
    -> nanoseconds of the trace's clock, through one point: ``window``
    is the ``bench.window`` annotation as the trace holds it and
    ``t_mark`` the host's clock read as it opened."""
    shift = window[0] - t_mark * 1e9
    return [(n, a * 1e9 + shift, b * 1e9 + shift) for n, a, b in marks]


def reduce_trace(loaded: dict, marks=None, window=None) -> dict:
    """``marks``: [(name, start_ns, end_ns)] on the trace's clock, or
    None to take the host's ``bench.*`` annotations from the trace.
    ``window``: (start_ns, end_ns); default the ``bench.window``
    annotation, else the span of all device events."""
    if marks is None:
        marks = loaded["host_marks"]
    if window is None:
        window = window_of(loaded)
    n_dev = len(loaded["devices"])
    busy_ns, by_program, gaps = [], {}, {}
    for dev in loaded["devices"]:
        ops, modules = dev["ops"], dev["modules"]
        running = ops or modules
        if window is None and running:
            window = (min(s for _, s, _ in running),
                      max(e for _, _, e in running))
        lo, hi = window if window else (0, 0)
        merged = _union((max(s, lo), min(e, hi)) for _, s, e in running
                        if e > lo and s < hi)
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, e in modules:
            if e > lo and s < hi:
                p = program_name(name)
                by_program[p] = by_program.get(p, 0.0) + (e - s) / 1e9
        # idle gaps of this device, by what the host was doing
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            cover = [m for m in marks
                     if m[1] <= mid <= m[2] and m[0] != WINDOW_MARK]
            name = (min(cover, key=lambda m: m[2] - m[1])[0]
                    if cover else "unmarked")
            gaps[name] = gaps.get(name, 0.0) + (g1 - g0) / 1e9 / n_dev
    window_s = (window[1] - window[0]) / 1e9 if window else 0.0

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": sum(busy_ns) / 1e9 / n_dev, "window_s": window_s,
            "program_s": by_program, "device_ops": top(by_program),
            "idle_gaps": top(gaps),
            "devices": n_dev}
