"""Chip smoke: the Driver's admission path on the TPU, at north-star width.

One process.  Resolves the device first and refuses anything but a TPU,
then drives the main path through the entry points a user calls, at the
full width of the north-star configuration (scripts/northstar_e2e.py's
own builder and defaults: 1,000 ClusterQueues in 200 cohorts x 100,000
pending workloads, a 1,000-gang priority-200 preemptor wave mid-run,
runtime 4 cycles; weights there are none — the cluster is generated
from the builder's fixed schedule):

  A. per-cycle engine  solver.warmup, then schedule_once cycles against
                       a host twin (use_device_solver=False) built by
                       the same generator;
  B. fused burst       a fresh driver, one schedule_burst with the wave
                       arriving mid-call, against a host twin;
  C. requests          AdmissionService.submit/step on the device driver
                       until every accepted token holds QuotaReserved.

Per compared cycle the admitted list (in order in A; as a set in B,
where decisions are applied in heads order), the sorted preemption
targets and the sorted skipped keys must equal the twin's.  Placement is
asserted from the solvers' counters, which are read off the device sets
of the kernels' outputs (ops/device.py output_devices).  Compiles
are counted with jax.monitoring: warm-up may compile or load, the
compared cycles may do neither.  Any failed assertion or exception is a
non-zero exit with no result line; nothing is caught.

With KUEUE_TPU_SHARDS=N (a multi-chip host) the same phases run through
the (wl, cq) mesh and the ("cq",) burst mesh and must show sharded
dispatches and outputs spread over N devices.

Debugging on the CPU: ``JAX_PLATFORMS=cpu python chip_smoke.py
--allow-cpu --cqs 12 --wl 240`` relaxes the device gate and nothing
else.

Stdout is two JSON lines: {"report": ...} with the counters and set-up
cost, then, last, {"ok": true, "device": {"platform", "kind", "count"}}
with exactly those keys (the driver's contract).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# burst counters the placement checks read (reported even when zero)
_BURST_KEYS = ("burst_dispatches", "burst_accel_dispatches",
               "burst_dirty_cycles", "burst_output_devices")
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

    def mark(self):
        return (self.requests, self.loaded)

    def since(self, mark) -> dict:
        req = self.requests - mark[0]
        loaded = self.loaded - mark[1]
        return {"programs": req, "compiled": req - loaded,
                "loaded_from_cache": loaded}


def check(cond, what, detail=None):
    if not cond:
        raise AssertionError(f"{what}: {detail}" if detail is not None
                             else what)


def compare_cycle(phase, cycle, dev_stats, host_stats, ordered=True):
    """``ordered=False`` for the fused path: it applies a cycle's
    decisions in heads order (Scheduler.apply_burst_cycle), so the list
    order in its stats is not the scan order, and the repo's own burst
    parity compares the cycle's admitted set (tests/test_burst.py)."""
    norm = list if ordered else sorted
    check(norm(dev_stats.admitted) == norm(host_stats.admitted),
          f"phase {phase} cycle {cycle}: admitted diverged",
          (len(dev_stats.admitted), len(host_stats.admitted)))
    check(sorted(dev_stats.preempted_targets)
          == sorted(host_stats.preempted_targets),
          f"phase {phase} cycle {cycle}: preemption targets diverged")
    check(sorted(dev_stats.skipped) == sorted(host_stats.skipped),
          f"phase {phase} cycle {cycle}: skipped diverged")
    return {"admitted": len(dev_stats.admitted),
            "preempting": len(dev_stats.preempting),
            "preempted": len(dev_stats.preempted_targets),
            "skipped": len(dev_stats.skipped)}


class PerCycle:
    """One generated cluster driven a cycle at a time through
    schedule_once, as scripts/northstar_e2e.py run_path drives it.  With
    use_device=False it is the host twin: the scalar scheduler on the
    same cluster, the reference the device decisions must equal."""

    def __init__(self, ns, args, use_device):
        self.d, self.clock, _, self.wave = ns.build(
            args.cqs, args.wl, use_device=use_device)
        self.runtime = args.runtime
        self.running = []
        self.cycle = 0

    def step(self, inject: bool):
        if inject:
            self.wave(self.clock.t)
        self.clock.t += 1.0
        stats = self.d.schedule_once()
        # fake execution: a workload admitted at cycle j finishes at the
        # end of cycle j+runtime unless it lost its reservation since
        for key in stats.admitted:
            self.running.append((self.cycle + self.runtime, key))
        still = []
        for fin, key in self.running:
            wl = self.d.workloads.get(key)
            if wl is None or not wl.has_quota_reservation:
                continue
            if fin <= self.cycle:
                self.d.finish_workload(key)
            else:
                still.append((fin, key))
        self.running = still
        self.cycle += 1
        return stats


def phase_a(ns, args, compiles):
    dev = PerCycle(ns, args, use_device=True)
    solver = dev.d.scheduler.solver
    m0 = compiles.mark()
    t0 = time.perf_counter()
    solver.warmup(dev.d.cache.snapshot(), args.cqs)
    warm = dict(compiles.since(m0),
                seconds=round(time.perf_counter() - t0, 1))
    print(f"phase A warm-up: {warm}", file=sys.stderr)

    twin = PerCycle(ns, args, use_device=False)
    cycles = []
    m1 = compiles.mark()
    for cycle in range(args.cycles_a):
        inject = cycle == args.inject_a
        cycles.append(compare_cycle("A", cycle, dev.step(inject),
                                    twin.step(inject)))
        print(f"phase A cycle {cycle}: {cycles[-1]}", file=sys.stderr)
    in_cycles = compiles.since(m1)
    check(in_cycles["programs"] == 0,
          "phase A built programs inside the compared cycles", in_cycles)
    check(sum(c["preempted"] for c in cycles) > 0
          and sum(c["skipped"] for c in cycles) > 0,
          "phase A never preempted or skipped: the wave missed", cycles)
    out = {"cycles": cycles, "warmup": warm,
           "compiles_in_cycles": in_cycles["programs"],
           "solver_stats": {k: v for k, v in solver.stats.items()
                            if not isinstance(v, dict)},
           "preemptor_stats": dict(dev.d.scheduler.preemptor.stats)}
    return dev.d, out


def phase_b(ns, args, compiles, shards):
    d, clock, _, wave = ns.build(args.cqs, args.wl, use_device=True)
    solver = d.scheduler.solver
    m0 = compiles.mark()
    t0 = time.perf_counter()
    bs = ns.warm_burst(d, clock, args.cqs, args.runtime, shards=shards)
    warm = dict(compiles.since(m0),
                seconds=round(time.perf_counter() - t0, 1))
    print(f"phase B warm-up: {warm}", file=sys.stderr)

    twin = PerCycle(ns, args, use_device=False)
    cycles = []

    def on_cycle_start(k):
        if k == args.inject_b:
            wave(clock.t)
        clock.t += 1.0

    def on_cycle(k, stats):
        cycles.append(compare_cycle(
            "B", k, stats, twin.step(k == args.inject_b), ordered=False))
        print(f"phase B cycle {k}: {cycles[-1]}", file=sys.stderr)

    m1 = compiles.mark()
    applied = d.schedule_burst(args.cycles_b, runtime=args.runtime,
                               on_cycle_start=on_cycle_start,
                               on_cycle=on_cycle)
    in_cycles = compiles.since(m1)
    check(len(applied) == args.cycles_b == len(cycles),
          "phase B did not apply every cycle", (len(applied), len(cycles)))
    check(in_cycles["programs"] == 0,
          "phase B built programs inside the compared cycles", in_cycles)
    check(sum(c["preempted"] for c in cycles) > 0,
          "phase B never preempted: the wave missed", cycles)
    out = {"cycles_applied": len(applied),
           "admitted": sum(c["admitted"] for c in cycles),
           "preempted": sum(c["preempted"] for c in cycles),
           "skipped": sum(c["skipped"] for c in cycles),
           "warmup": warm, "compiles_in_cycles": in_cycles["programs"],
           "burst_stats": {k: v for k, v in bs.stats.items()
                           if isinstance(v, int)
                           and (v or k in _BURST_KEYS)},
           # the per-cycle engine's share of this phase: truncated
           # windows finish on it (warm-up launches are not counted)
           "solver_stats": {k: v for k, v in solver.stats.items()
                            if not isinstance(v, dict)},
           "preemptor_stats": dict(d.scheduler.preemptor.stats)}
    return out


def phase_c(d, args, compiles):
    from kueue_tpu.serving.service import AdmissionService, ServiceConfig
    svc = AdmissionService(d, ServiceConfig(dt_s=1.0, k_max=1,
                                            journal_path=""))
    m0 = compiles.mark()
    tokens = []
    for i in range(args.requests):
        res = svc.submit(name=f"req-{i}",
                         queue_name=f"lq-{(i * 37) % args.cqs}",
                         requests={"cpu": 500}, priority=1000)
        check(res.status == "accepted", "phase C submit", res)
        tokens.append(res.token)

    def reserved(token):
        wl = d.workloads.get(token)
        return wl is not None and wl.has_quota_reservation

    steps = 0
    while not all(reserved(t) for t in tokens):
        check(steps < args.max_steps,
              "phase C: accepted tokens without QuotaReserved",
              [t for t in tokens if not reserved(t)])
        svc.step()
        steps += 1
    return {"submitted": len(tokens), "reserved": len(tokens),
            "steps": steps,
            "compiles": compiles.since(m0)["programs"]}


def placement_checks(a, b, on_tpu, shards):
    """Proof of placement, from counters that are read off the kernels'
    output device sets.  On the relaxed CPU gate the same counters must
    show the mirror image: everything on the XLA:CPU device."""
    here, there = (("accel", "cpu") if on_tpu else ("cpu", "accel"))
    for name, ss in (("A", a["solver_stats"]), ("B", b["solver_stats"])):
        check(ss[f"{there}_dispatches"] == 0,
              f"phase {name}: admit scans ran off the device", ss)
        check(ss["host_cycles"] == 0 and ss["scalar_heads"] == 0,
              f"phase {name}: cycles or heads fell to the host", ss)
    check(a["solver_stats"][f"{here}_dispatches"] > 0,
          "phase A: no admit scan reached the device", a["solver_stats"])
    bs = b["burst_stats"]
    check(bs["burst_dispatches"] > 0, "phase B: no burst dispatch", bs)
    check(bs["burst_accel_dispatches"]
          == (bs["burst_dispatches"] if on_tpu else 0),
          "phase B: burst windows ran off the device", bs)
    check(bs["burst_dirty_cycles"] == 0,
          "phase B: cycles outside the fused kernel's envelope", bs)
    for name, ps in (("A", a["preemptor_stats"]),
                     ("B", b["preemptor_stats"])):
        check(ps["host_searches"] == 0
              and ps["accel_searches"]
              == (ps["device_searches"] if on_tpu else 0),
              f"phase {name}: preemption searches ran off the device", ps)
    check(a["preemptor_stats"]["device_searches"] > 0,
          "phase A: no device preemption search", a["preemptor_stats"])
    if shards > 1:
        sa = a["solver_stats"]
        check(sa.get("sharded_dispatches", 0) > 0
              and sa["output_devices"] == shards,
              "phase A: admit scans not spread over the mesh", sa)
        check(bs.get("burst_sharded_dispatches", 0)
              == bs["burst_dispatches"]
              and bs["burst_output_devices"] == shards,
              "phase B: burst windows not spread over the mesh", bs)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cqs", type=int, default=1000)
    ap.add_argument("--wl", type=int, default=100_000)
    ap.add_argument("--runtime", type=int, default=4)
    ap.add_argument("--cycles-a", type=int, default=6)
    ap.add_argument("--inject-a", type=int, default=3)
    ap.add_argument("--cycles-b", type=int, default=32)
    ap.add_argument("--inject-b", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-steps", type=int, default=8)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="debugging only: relax the device gate")
    args = ap.parse_args()

    # -- device gate: before anything else of the repo ------------------
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    print(f"device: {device} jax {jax.__version__}", file=sys.stderr)
    if not on_tpu and not args.allow_cpu:
        sys.exit(f"chip_smoke: the default JAX backend is "
                 f"{dev.platform!r} ({dev.device_kind}), not a TPU; "
                 f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}. "
                 f"Run it through chiprun, or debug with --allow-cpu.")
    compiles = CompileCounter()

    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import northstar_e2e as ns
    from kueue_tpu import compilecache
    from kueue_tpu.features import env_int
    shards = env_int("KUEUE_TPU_SHARDS")
    cache_dir = compilecache.enable()
    print(f"compile cache: {cache_dir} shards: {shards}", file=sys.stderr)

    t0 = time.perf_counter()
    d_a, a = phase_a(ns, args, compiles)
    # the finished phase's host twin is a frozen cyclic graph of 100k
    # workloads: un-freeze so it is collectable before the next build
    gc.unfreeze()
    gc.collect()
    b = phase_b(ns, args, compiles, shards)
    gc.unfreeze()
    gc.collect()
    placement_checks(a, b, on_tpu, shards)
    c = phase_c(d_a, args, compiles)

    # the report (counters, set-up cost) on its own line, then the result
    # line, which carries the verdict and the device and nothing else
    print(json.dumps({"report": {
        "jax": jax.__version__,
        "compile_cache": cache_dir,
        "shards": max(1, shards),
        "width": {"cqs": args.cqs, "workloads": args.wl},
        "phase_a": a, "phase_b": b, "phase_c": c,
        "wall_s": round(time.perf_counter() - t0, 1),
    }}))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
