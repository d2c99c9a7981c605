"""Traffic kind ``burst_rounds``: boundaries of completions, then a burst.

A round is one boundary, at which every queue finishes a fixed fraction
of what it then runs (which workloads: drawn from a stream of the
tenant's own, keyed by its rank and not by the seed, so that every seed
runs the same cluster under other names; fractions below one workload
are carried to the next round), through
``Driver.finish_workloads``, and then one
``Driver.schedule_burst(cycles_per_round, runtime)``.  Nothing arrives.
The parameters come from the traffic's data file.  What the round fed
the program (the finished keys, the clock at each cycle) and what the
program answered (each applied cycle's decisions) go into the record
that the plain reference replays afterwards.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class CycleRecord:
    clock: float
    admitted: list
    evicted: list
    skipped: list
    preempting: list
    heads: int
    seconds: float


@dataclass
class RoundRecord:
    finished: list
    cycles: list = field(default_factory=list)
    max_cycles: int = 0
    boundary_s: float = 0.0
    seconds: float = 0.0


class Traffic:
    def __init__(self, params: dict, plan, seed: int):
        self.fraction = float(params["finish_fraction_per_round"])
        self.max_cycles = int(params["cycles_per_round"])
        self.runtime = int(params["runtime"])
        self.warm_rounds = int(params.get("warm_rounds", 1))
        # a warm round may be cut short: it has to launch the window
        # once and run the per-cycle engine, not settle the cluster
        self.warm_cycles = int(params.get("warm_cycles", self.max_cycles))
        # a traced run's profiler is on for this many cycles of the first
        # measured round (default: until the window closes)
        self.trace_cycles = int(params.get("trace_cycles", 1 << 30))
        self.cycle_s = plan.cycle_s
        # the seed is in the plan's labels and nowhere else
        self.rngs = [np.random.default_rng([0x726F756E, q.rank])
                     for q in plan.queues]
        n_q = len(plan.queues)
        self.queue_of = {}
        self.row = {}            # key -> the plan's row: queue, then k
        self.running: list[list] = [[] for _ in range(n_q)]
        self.slot: dict[str, int] = {}
        q_of = plan.wl_queue.tolist()
        run = plan.wl_running.tolist()
        for i in range(len(q_of)):
            k = plan.key(i)
            self.queue_of[k] = q_of[i]
            self.row[k] = i
            if run[i]:
                self._add(k)
        self.carry = [0.0] * n_q

    def _add(self, key):
        lst = self.running[self.queue_of[key]]
        self.slot[key] = len(lst)
        lst.append(key)

    def _remove(self, key):
        j = self.slot.pop(key, None)
        if j is None:
            return
        lst = self.running[self.queue_of[key]]
        last = lst.pop()
        if last != key:
            lst[j] = last
            self.slot[last] = j

    def draw_finishes(self) -> list:
        out = []
        for c, lst in enumerate(self.running):
            self.carry[c] += len(lst) * self.fraction
            n = int(self.carry[c])
            if n <= 0:
                continue
            self.carry[c] -= n
            n = min(n, len(lst))
            for j in self.rngs[c].choice(len(lst), size=n, replace=False):
                out.append(lst[int(j)])
        for k in out:
            self._remove(k)
        return out

    def round(self, driver, clock, mark=None, max_cycles=None,
              after_cycle=None) -> RoundRecord:
        """One round through the program.  ``mark(name, t0, t1)`` takes
        host marks for the trace's idle gaps; ``after_cycle(k)`` runs
        once cycle k of the round has been recorded, and its time is
        no cycle's."""
        max_cycles = max_cycles or self.max_cycles
        t_round = time.perf_counter()
        finished = self.draw_finishes()
        rec = RoundRecord(finished=finished, max_cycles=max_cycles)
        t0 = time.perf_counter()
        driver.finish_workloads(finished)
        t1 = time.perf_counter()
        rec.boundary_s = t1 - t0
        if mark is not None:
            mark("bench.draw_finishes", t_round, t0)
            mark("bench.boundary", t0, t1)
        last = [t1]
        started = [t1]

        def on_cycle_start(_k):
            clock.t += self.cycle_s
            started[0] = time.perf_counter()

        def on_cycle(_k, stats):
            now = time.perf_counter()
            heads = (len(stats.admitted) + len(stats.skipped)
                     + len(stats.inadmissible))
            rec.cycles.append(CycleRecord(
                clock=clock.t, admitted=list(stats.admitted),
                evicted=list(stats.preempted_targets),
                skipped=list(stats.skipped),
                preempting=list(stats.preempting), heads=heads,
                seconds=now - last[0]))
            # in the plan's order, so that a queue's list, and what is
            # drawn from it next, does not depend on the program's order
            for k in sorted(stats.preempted_targets, key=self.row.get):
                self._remove(k)
            for k in stats.admitted:
                self._add(k)
            if mark is not None:
                mark("bench.cycle", started[0], now)
            if after_cycle is not None:
                after_cycle(len(rec.cycles) - 1)
            last[0] = time.perf_counter()

        driver.schedule_burst(max_cycles, runtime=self.runtime,
                              on_cycle_start=on_cycle_start,
                              on_cycle=on_cycle)
        t2 = time.perf_counter()
        if mark is not None:
            mark("bench.schedule_burst", t1, t2)
        rec.seconds = t2 - t_round
        return rec
