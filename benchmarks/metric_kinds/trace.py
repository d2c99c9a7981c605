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
  nothing is clipped.

No trace, or no event of the program in it, is nothing to read.
"""

import peaks


def _program_s(spec, trace):
    hits = [s for name, s in trace["program_s"].items()
            if name in spec["programs"]]
    return sum(hits) if hits else None


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    what = spec["what"]
    if what == "idle_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    seconds = _program_s(spec, trace)
    launches = ctx["counters"]["traced"].get(spec["launch_counter"], 0)
    if not seconds or not launches:
        return None
    if what == "program_ms":
        return seconds / launches * 1e3
    if what == "roofline_pct":
        p = ctx["problem"]
        least = (peaks.burst_launch_bytes(p["real_rows"], p["queues"],
                                          p["resources"]) * launches
                 / peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"])
        return 100.0 * least / seconds
    raise ValueError(f"trace reader: unknown {what!r}")
