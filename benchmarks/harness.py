"""One run of one cell: set-up, measured window, comparison, metrics.

Driven by data.  ``BENCHMARK.json`` names the cell's configuration file
and its traffic.  The configuration's file names its deployment kind
(``"kind"``), found as the package ``deployment_kinds/<kind>/``; the
traffic's data file (``traffic/<traffic>.json``) names its kind, found
as ``traffic_kinds/<kind>.py``; each per-layer metric is
``metrics/<name>.json`` naming a reader ``metric_kinds/<kind>.py``.  A
later PR adds a deployment, a mix or a metric as new files and entries,
and edits nothing here.

The contract of a deployment kind
---------------------------------
Whatever is particular to one shape of cluster (how a configuration
becomes queues and workloads, how the program's objects are declared,
the plain reference's semantics, the capacity model of the quota
ledger) lives in the kind's package.  The harness, ``correct.py`` and
``control.py`` call of a kind the names of ``KIND_CONTRACT`` and nothing
else, and import no kind by name
(benchmarks/tests/test_deployment_kinds.py holds both):

- ``plan_cluster(cfg, seed) -> plan``: the cluster as plain data, from
  the configuration and the seed alone, importing nothing of the
  program.  Of a plan the harness itself reads only ``config``,
  ``clock_start`` and ``cycle_s``.
- ``summary(plan) -> str``: one line for the run's log.
- ``problem(cfg, plan) -> dict``: what benchmarks/peaks.py counts a
  decided cycle's bytes from: ``real_rows``, ``queues``, ``resources``.
- ``build_driver(plan) -> (driver, clock)``: the system under test,
  restored and ingested; ``clock.t`` is the virtual time the traffic
  advances.
- ``warm_up(driver, plan) -> dict``: every shape the cell's cycles can
  reach, compiled or loaded; what it returns goes to the log.
- ``Reference(plan, broken=None)``: the plain reference, which imports
  nothing of the program, with ``begin_round(round_record) -> int``
  (takes the round's inputs from the traffic's record, whatever the
  traffic kind wrote there; returns how many of them it does not know),
  ``cycle(clock) -> result`` and ``has_heads() -> bool``.  A result
  holds every field of ``COMPARED``, and ``evicted`` and
  ``cross_queue_evictions`` for the facts beside the verdict.
  ``broken`` is one of ``CONTROLS``: a guarantee the configuration
  states, switched off.
- ``CONTROLS``: the names ``broken`` takes; ``control.py`` puts each in
  the program's place and the comparison has to fail it.
- ``COMPARED``: the per-cycle fields that ``correct.compare`` holds equal,
  order apart, between the traffic's cycle record and the reference's
  result.  A kind that decides more (the flavor of each admission, say)
  names one more field here and its traffic kind fills it.
- ``ledger(plan, rounds) -> {"quota_violations", "double_admissions",
  "unknown_finishes"}``: the program's own answers added up against the
  kind's capacity model, trusting neither side.

What a traffic kind reads of a plan is the kind's to keep or not:
``burst_rounds`` reads ``queues[i].rank``, ``wl_queue``, ``wl_running``,
``key(i)`` and ``cycle_s``.  A kind whose plan has them reuses the
``backlog`` mix as it is; one that has not brings a traffic kind of its
own.  Of the traffic's records the harness and the comparison read: a
round's ``cycles``, ``max_cycles``, ``seconds`` and ``boundary_s``; a
cycle's ``clock``, ``admitted``, ``evicted``, ``heads``, ``seconds`` and
the fields of ``COMPARED``.
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

KIND_CONTRACT = ("plan_cluster", "summary", "problem", "build_driver",
                 "warm_up", "Reference", "CONTROLS", "COMPARED", "ledger")

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


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def deployment_kind(cfg: dict, where: str):
    """The module of the deployment kind a configuration names.  There
    is no default: a configuration replayed through another kind's
    reference would be compared with the wrong semantics."""
    kind = cfg.get("kind")
    if not isinstance(kind, str) or not kind.isidentifier():
        raise SystemExit(f"benchmark: configuration {where} names no "
                         f"deployment kind (\"kind\": {kind!r})")
    try:
        return _module("deployment_kinds", kind)
    except ModuleNotFoundError as e:
        if e.name != f"deployment_kinds.{kind}":
            raise
        raise SystemExit(f"benchmark: configuration {where} names the "
                         f"deployment kind {kind!r}, and there is no "
                         f"package deployment_kinds/{kind}/") from None


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
    output, before it is serialised).  ``on_rounds(kind, plan, rounds,
    measured_from)`` sees the run's record before the comparison: the
    control (benchmarks/control.py) replays it through a broken
    reference."""
    import gc
    gc.unfreeze()            # a second run in one process frees the first
    gc.collect()

    import correct

    cell, cfg_entry = find_cell(manifest, cell_name)
    cfg = load_config(os.path.join(root, cfg_entry["file"]))
    kind = deployment_kind(cfg, cfg_entry["file"])
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

    from kueue_tpu import compilecache
    say(t_start, f"compile cache: {compilecache.enable()}")

    # ---- set-up ----------------------------------------------------
    plan = kind.plan_cluster(cfg, seed)
    driver, clock = kind.build_driver(plan)
    say(t_start, kind.summary(plan))
    traffic = _module("traffic_kinds", traffic_params["kind"]).Traffic(
        traffic_params, plan, seed)
    warmed = kind.warm_up(driver, plan)
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
    trace_dir = window_mark = traced_records = None
    traced_s = pause_s = t_mark = 0.0
    counters_traced = {}

    def stop_trace(k=None):
        nonlocal window_mark, traced_s, pause_s, counters_traced
        nonlocal traced_records
        if window_mark is None or (k is not None
                                   and k + 1 < traffic.trace_cycles):
            return
        t0 = time.perf_counter()
        traced_s = t0 - t_w0
        counters_traced = _delta(program_counters(driver), counters_setup)
        if k is not None:      # the finished rounds, and k + 1 of this one
            traced_records = k + 1 + sum(
                len(r.cycles) for r in rounds[measured_from:])
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
        # the instant the annotation opened, for the spans' move onto the
        # trace's clock; the timed window opens at t_w0 below, as ever
        t_mark = time.perf_counter()
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
    done = [c for r in window for c in r.cycles]
    admissions = sum(len(c.admitted) for c in done)
    evictions = sum(len(c.evicted) for c in done)
    cycles = sum(1 for c in done if c.heads)
    # of them, those decided while the profiler was on
    traced_cycles = sum(1 for c in done[:traced_records] if c.heads)
    heads = sum(c.heads for c in done)
    slowest = max((c.seconds for c in done), default=0.0)
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
            on_trace = [] if w is None else trace_reduce.onto_trace_clock(
                w, t_mark, marks + [(s.name, s.t0, s.t0 + s.dur)
                                    for s in span_records])
            reduced = trace_reduce.reduce_trace(loaded, marks=on_trace)
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
        on_rounds(kind, plan, rounds, measured_from)
    verdict = correct.compare(kind, plan, rounds, measured_from)
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
        ctx = {
            "rounds": len(window), "cycles": cycles, "window_s": window_s,
            "traced_cycles": traced_cycles,
            "clocks": {"boundary_s": sum(r.boundary_s for r in window)},
            "spans": spans, "tracer_on": driver_traced,
            "counters": {"window": dict(counters_window, **window_compiles),
                         "setup": dict(counters_setup, **setup_compiles),
                         "traced": counters_traced},
            "memory_peak_bytes": peak,
            "trace": reduced,
            "device_kind": device["kind"],
            "problem": kind.problem(cfg, plan),
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
        cycle_s=[round(c.seconds, 3) for c in done],
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
