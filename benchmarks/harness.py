"""One run of one cell: set-up, measured window, comparison, metrics.

Driven by data.  ``BENCHMARK.json`` names the cell's configuration file
and its traffic; the traffic's data file (``traffic/<traffic>.json``)
names its kind, found as ``traffic_kinds/<kind>.py``; each per-layer
metric is ``metrics/<name>.json`` naming a reader
``metric_kinds/<kind>.py``.  A later PR adds a deployment, a mix or a
metric as new files and entries, and edits nothing here.
"""

from __future__ import annotations

import importlib
import json
import os
import resource
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Executable builds JAX performed, from its own monitoring events:
    every build request fires _COMPILE_EVENT, and the ones served from
    the persistent cache also fire _CACHE_HIT_EVENT."""

    def __init__(self):
        import jax.monitoring as m
        self.requests = 0
        self.loaded = 0
        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == _COMPILE_EVENT:
            self.requests += 1

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT_EVENT:
            self.loaded += 1

    def read(self) -> dict:
        return {"programs_built": self.requests,
                "programs_loaded_from_cache": self.loaded,
                "programs_compiled": self.requests - self.loaded}


def say(t0: float, msg: str) -> None:
    print(f"[bench {time.perf_counter() - t0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return cell, cfg


def _module(package: str, kind: str):
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module(f"{package}.{kind}")


def program_counters(driver) -> dict:
    """The program's integer and float counters, flat."""
    out = {}
    for stats in (getattr(driver._burst_solver, "stats", None),
                  getattr(driver.scheduler.solver, "stats", None),
                  driver.scheduler.preemptor.stats):
        for k, v in (stats or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = v
    return out


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def span_totals(driver) -> dict:
    t = driver.obs.tracer
    if t is None:
        return {}
    return {name: row["total_s"] for name, row in t.roster().items()}


def read_per_layer(manifest: dict, cell_name: str, ctx: dict,
                   root: str = ROOT) -> dict:
    """Every per-layer metric that lists this cell (or lists none),
    through its reader.  A reader that finds nothing returns None and
    the metric is left out."""
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        path = os.path.join(root, manifest["paths"][0], "metrics",
                            m["name"] + ".json")
        with open(path) as f:
            spec = json.load(f)
        value = _module("metric_kinds", spec["kind"]).read(spec, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, root: str = ROOT,
             require_tpu: bool = True, on_rounds=None) -> dict:
    """Returns the result object of the run (the last line of standard
    output, before it is serialised).  ``on_rounds(plan, rounds,
    measured_from)`` sees the run's record before the comparison: the
    control (benchmarks/control.py) replays it through a broken
    reference."""
    import gc
    gc.unfreeze()            # a second run in one process frees the first
    gc.collect()

    import cluster
    import correct
    import reference

    cell, cfg_entry = find_cell(manifest, cell_name)
    cfg = cluster.load_config(os.path.join(root, cfg_entry["file"]))
    with open(os.path.join(root, manifest["paths"][0], "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic_params = json.load(f)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(t_start, f"device: {device} jax {jax.__version__}")
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell["chips"]):
        raise SystemExit(
            f"benchmark: cell {cell_name} needs {cell['chips']} TPU "
            f"chip(s); JAX reports {device}")
    compiles = CompileCounter()

    import program
    from kueue_tpu import compilecache
    say(t_start, f"compile cache: {compilecache.enable()}")

    # ---- set-up ----------------------------------------------------
    plan = cluster.plan_cluster(cfg, seed)
    driver, clock = program.build_driver(plan, use_device=True)
    say(t_start, f"built {len(plan.queues)} queues, "
        f"{int(plan.wl_running.sum())} restored, "
        f"{int((~plan.wl_running).sum())} ingested")
    traffic = _module("traffic_kinds", traffic_params["kind"]).Traffic(
        traffic_params, plan, seed)
    cohort_rows = {}
    for q in plan.queues:
        cohort_rows[q.cohort] = cohort_rows.get(q.cohort, 0) + q.running
    warmed = program.warm_up(driver, len(plan.queues),
                             max(cohort_rows.values()))
    say(t_start, f"warm-up {warmed}: {compiles.read()}")
    rounds = []
    for _ in range(traffic.warm_rounds):
        rounds.append(traffic.round(driver, clock,
                                    max_cycles=traffic.warm_cycles))
        say(t_start, f"warm round: {rounds[-1].seconds:.2f} s, "
            f"{len(rounds[-1].cycles)} cycles")
    measured_from = len(rounds)
    setup_compiles = compiles.read()
    counters_setup = program_counters(driver)
    gc.collect()
    setup_s = time.perf_counter() - t_start
    say(t_start, f"set-up done: {setup_compiles}")

    # ---- measured window ----------------------------------------------
    # A traced run's profiler is on from the window's start through the
    # first ``traffic.trace_cycles`` cycles and is stopped between two
    # cycles: the trace of a whole round of this size takes three
    # minutes to write, of the six a run may last.  The pause is taken
    # out of the traced run's window, which reports no end-to-end metric.
    marks = []
    trace_dir = window_mark = None
    traced_s = pause_s = 0.0
    counters_traced = {}

    def stop_trace(k=None):
        nonlocal window_mark, traced_s, pause_s, counters_traced
        if window_mark is None or (k is not None
                                   and k + 1 < traffic.trace_cycles):
            return
        t0 = time.perf_counter()
        traced_s = t0 - t_w0
        counters_traced = _delta(program_counters(driver), counters_setup)
        window_mark.__exit__(None, None, None)
        window_mark = None
        jax.profiler.stop_trace()
        pause_s = time.perf_counter() - t0
        say(t_start, f"profiler: on for the window's first {traced_s:.1f} s"
            f" ({'the whole window' if k is None else f'{k + 1} cycles'}), "
            f"{pause_s:.1f} s to stop")

    if trace:
        driver.obs.enable_tracing()
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_mark = jax.profiler.TraceAnnotation("bench.window")
        window_mark.__enter__()
    spans0 = span_totals(driver)
    mark = (lambda n, a, b: marks.append((n, a, b))) if trace else None
    usage0 = resource.getrusage(resource.RUSAGE_SELF)
    t_w0 = time.perf_counter()
    while True:
        rounds.append(traffic.round(
            driver, clock, mark,
            after_cycle=stop_trace if window_mark is not None else None))
        if time.perf_counter() - t_w0 - pause_s >= seconds:
            break
    t_w1 = time.perf_counter()
    if window_mark is not None:       # the window closed first
        stop_trace()
        pause_s = 0.0
    window_s = t_w1 - t_w0 - pause_s
    usage1 = resource.getrusage(resource.RUSAGE_SELF)
    # what the host gave the window: one that waits for a core shows
    # here (processor time well under the wall) and not in the program
    host = {"user_s": usage1.ru_utime - usage0.ru_utime,
            "system_s": usage1.ru_stime - usage0.ru_stime}
    window_compiles = _delta(compiles.read(), setup_compiles)
    counters_window = _delta(program_counters(driver), counters_setup)
    spans = _delta(span_totals(driver), spans0)
    span_records = (list(driver.obs.tracer.trace_spans)
                    if trace and driver.obs.tracer is not None else [])
    window = rounds[measured_from:]
    admissions = sum(len(c.admitted) for r in window for c in r.cycles)
    evictions = sum(len(c.evicted) for r in window for c in r.cycles)
    cycles = sum(1 for r in window for c in r.cycles if c.heads)
    heads = sum(c.heads for r in window for c in r.cycles)
    slowest = max((c.seconds for r in window for c in r.cycles),
                  default=0.0)
    say(t_start, f"window: {len(window)} rounds "
        f"{[round(r.seconds, 3) for r in window]} s, {cycles} cycles, "
        f"{admissions} admissions, {evictions} evictions, slowest "
        f"cycle {slowest * 1e3:.1f} ms, compiles {window_compiles}, "
        f"host {host}")

    stats = devices[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    device["memory_peak_bytes"] = int(peak)
    say(t_start, f"memory: peak {peak / 2**30:.3f} GiB, now "
        f"{stats.get('bytes_in_use', 0) / 2**30:.3f} GiB")

    # ---- per-layer inputs -------------------------------------------------
    reduced = None
    if trace:
        import trace_reduce
        path = trace_reduce.find_xplane(trace_dir)
        loaded = trace_reduce.load_trace(path) if path else None
        if loaded is not None:
            # the program's spans and the benchmark's marks, moved onto
            # the trace's clock through the bench.window annotation
            w = trace_reduce.window_of(loaded)
            host = []
            if w is not None:
                shift = w[0] - t_w0 * 1e9
                host = [(n, a * 1e9 + shift, b * 1e9 + shift)
                        for n, a, b in marks]
                host += [(s.name, s.t0 * 1e9 + shift,
                          (s.t0 + s.dur) * 1e9 + shift)
                         for s in span_records]
            reduced = trace_reduce.reduce_trace(loaded, marks=host)
        if path is not None:
            size = os.path.getsize(path)
            say(t_start, f"trace: {size / 1e6:.1f} MB, reduced "
                f"{'ok' if reduced else 'nothing on a device plane'}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]

    # ---- correct: after the window, the peak read, the trace reduced ----
    driver_traced = driver.obs.tracer is not None
    driver.obs.disable_tracing()
    del driver
    if on_rounds is not None:
        on_rounds(plan, rounds, measured_from)
    verdict = correct.compare(plan, rounds, measured_from,
                              reference.Reference)
    facts = verdict["facts"]
    say(t_start, f"reference: {facts['reference_s']:.2f} s over "
        f"{facts['cycles_compared']} cycles; cycles with evictions "
        f"{facts['cycles_with_evictions']}, evictions "
        f"{facts['evictions']}, of them across queues "
        f"{facts['cross_queue_evictions']}")
    if facts["first_mismatch"] is not None:
        say(t_start, f"first mismatch: {facts['first_mismatch']}")

    end_to_end = {
        "admissions_per_s": admissions / window_s,
        "cycle_ms": window_s / max(1, cycles) * 1e3,
        "setup_s": setup_s,
    }
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    if trace:
        rows = cluster.queue_rows(cfg)
        ctx = {
            "rounds": len(window), "cycles": cycles, "window_s": window_s,
            "clocks": {"boundary_s": sum(r.boundary_s for r in window)},
            "spans": spans, "tracer_on": driver_traced,
            "counters": {"window": dict(counters_window, **window_compiles),
                         "setup": dict(counters_setup, **setup_compiles),
                         "traced": counters_traced},
            "memory_peak_bytes": peak,
            "trace": reduced,
            "device_kind": device["kind"],
            "problem": {"real_rows": rows["preempting_forest_rows"],
                        "queues": len(plan.queues),
                        "resources": len(plan.resources)},
        }
        metrics = read_per_layer(manifest, cell_name, ctx, root)
    else:
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in end_to_end.items() if k in units}

    result = {
        "correct": bool(verdict["correct"]),
        "attempted": heads, "failed": 0,
        "metrics": metrics, "device": device,
    }
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["facts"] = dict(
        end_to_end, window_s=window_s, rounds=len(window), cycles=cycles,
        admissions=admissions, evictions=evictions,
        slowest_cycle_ms=slowest * 1e3,
        cycle_s=[round(c.seconds, 3) for r in window for c in r.cycles],
        host=host,
        cycles_compared=facts["cycles_compared"],
        cycles_with_evictions=facts["cycles_with_evictions"],
        cross_queue_evictions=facts["cross_queue_evictions"],
        reference_s=facts["reference_s"],
        setup_programs=setup_compiles, window_programs=window_compiles,
        host_searches=counters_window.get("host_searches", 0),
        device_searches=counters_window.get("device_searches", 0))
    result["compared"] = verdict["compared"]
    for name, row in verdict["compared"].items():
        print(f"compared {name}: value {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return result
