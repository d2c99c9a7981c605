"""Reader ``trace``: numbers from the reduced profiler trace
(benchmarks/trace_reduce.py).  The trace covers the window's first
cycles, as many as the traffic names (``trace_cycles``), and nothing
here is scaled up to a round.  Spec ``what``:

- ``idle_pct``: 100 x (1 - busy / traced window);
- ``program_ms``: device time of the programs named in ``programs``, a
  launch the trace holds (``launch_counter``, counted while it ran);
- ``roofline_pct``: the least time the chip could take for those
  launches, over their device time.  The least time is the bytes that
  benchmarks/peaks.py counts for one launch, times the launches, over
  the chip's HBM bandwidth.  Never above 100 unless the count is wrong;
  nothing is clipped;
- ``decide_roofline_pct``: the least time the chip could take to decide
  the cycles the trace holds, over the device's busy time in it, every
  program counted.  The least time is the same bytes (what one decided
  cycle has to read, counted from the cluster) times the cycles with a
  head decided while the profiler ran, over the HBM bandwidth.  It reads
  the same work whatever program decides it, so it still speaks when a
  kernel leaves the path.

No trace, or no event of the program in it, is nothing to read.
"""

import peaks


def _program_s(spec, trace):
    hits = [s for name, s in trace["program_s"].items()
            if name in spec["programs"]]
    return sum(hits) if hits else None


def _least_s(ctx, times):
    p = ctx["problem"]
    return (peaks.burst_launch_bytes(p["real_rows"], p["queues"],
                                     p["resources"]) * times
            / peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"])


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    what = spec["what"]
    if what == "idle_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if what == "decide_roofline_pct":
        if not trace["busy_s"] or not ctx["traced_cycles"]:
            return None
        return 100.0 * _least_s(ctx, ctx["traced_cycles"]) / trace["busy_s"]
    seconds = _program_s(spec, trace)
    launches = ctx["counters"]["traced"].get(spec["launch_counter"], 0)
    if not seconds or not launches:
        return None
    if what == "program_ms":
        return seconds / launches * 1e3
    if what == "roofline_pct":
        return 100.0 * _least_s(ctx, launches) / seconds
    raise ValueError(f"trace reader: unknown {what!r}")
