"""Structured span tracer for the admission hot path.

The tracer follows the chaos-injector pattern: a module-global
``ACTIVE`` that every instrumented site consults.  Tracing off
(``ACTIVE is None``) costs one module-attribute read and a ``with`` on
a shared no-op singleton — no allocation, no clock read, no branch into
tracer code.  Tracing on, each ``span(name)``:

- reads the wall clock (``time.perf_counter``) at entry and exit,
- reads the *virtual* clock (the driver's ``clock``) once at entry when
  one is attached — a pure read, never a tick, so traced and untraced
  runs make bit-identical decisions,
- feeds the duration into the registry's per-phase exponential-bucket
  histogram (``kueue_span_duration_seconds{phase=...}``),
- when it had at least one recorded child, feeds its *self time*
  (duration less the children's) into the same series under the phase
  ``<name>.self``, so a parent says how much of it no sub-span covers,
- holds a ``jax.profiler.TraceAnnotation(name)`` open for its lifetime:
  whenever a profiler session is running, whoever started it
  (``profiling.start_trace``, ``cli --profile-dir``, a benchmark), the
  span sits on the trace's ``/host:CPU`` plane, on the trace's own
  clock, beside the device's operations — one system, not two,
- appends a finished-span record to the current cycle buffer, which the
  flight recorder drains at each cycle boundary (``counted=True``
  leaves skip the record, the annotation and the self-time bookkeeping
  and keep histogram-only timing — see :func:`span`).

``Tracer.trace_spans`` keeps the first ``trace_capacity`` records for
``/debug/spans``; what it refuses is counted in ``dropped_total``.

Spans nest via an explicit stack; ``Span.__exit__`` enforces LIFO
pairing (a span may close exactly once, and only when it is the
innermost open span), so malformed instrumentation fails loudly in
tests instead of producing silently garbled traces.

While a tracer is installed the cyclic collector's runs are spans too
(``host.gc``, through ``gc.callbacks``: :func:`_on_gc`), nested in
whatever span was open: a collection in the middle of a phase is the
collector's time, not that phase's self time.

``to_chrome_trace`` renders finished spans as Chrome trace-event JSON
(``ph: "X"`` complete events, microsecond ``perf_counter`` timestamps)
for ``/debug/spans`` when no profiler session ran; a profiler trace
already holds the same spans as annotations, on its own clock.

``jax`` is imported where a :class:`Tracer` is built, never at the
import of this module: the WAL and the federation reach :func:`span`
(a no-op while tracing is off) without it.
"""

from __future__ import annotations

import gc
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

from ..metrics import Histogram, Registry, exponential_buckets

#: Exponential buckets for per-phase span durations: 1µs .. ~4s.
SPAN_BUCKETS = exponential_buckets(1e-6, 2, 22)

#: Every phase the hot path is instrumented with, in call order.  The
#: OBS artifact's span roster and the SIGUSR2 dump are checked against
#: this list; adding an instrumentation site means adding its name here.
#: A dotted child is opened only inside its parent (the reclaim
#: oracle's searches reuse the search's five phases one level deeper,
#: inside ``cycle.nominate.oracle``); the roster also carries
#: ``<name>.self`` for every phase that had a child.
HOT_PATH_PHASES = (
    "queue.heads",      # a cycle's heads popped off the queues (and, in
                        # schedule_burst, the timers fired before them)
    "cycle",            # one whole scheduling cycle (schedule_once path)
    "cycle.snapshot",   # cache snapshot build / incremental reuse
    "cycle.nominate",   # validation + flavor assignment + preempt targets
    "cycle.nominate.validate",        # the per-head loop that builds Entries
    "cycle.nominate.classify",        # cycle pack + device classification
    "cycle.nominate.classify.eligibility",  # the heads' [W, G, S] flavor plane
    "cycle.nominate.classify.podsets",  # a pass a PodSet of every head
    "cycle.nominate.classify.groups",  # one flavor walk a group, joined
    "cycle.nominate.walk",            # host FlavorAssigner walks
    "cycle.nominate.oracle",          # the reclaim oracle's batched searches
    "cycle.nominate.candidates",      # find/sort candidates, plan searches
    "cycle.nominate.search_pack",     # numpy fill of the [S, K, F] planes
    "cycle.nominate.search_launch",   # planes up, the search, results back
    "cycle.nominate.search_decode",   # masks to Target lists
    "cycle.nominate.search_fallback",  # the one-launch-a-head route
    "cycle.nominate.scan_dispatch",   # pack targets + admit-scan dispatch
    "cycle.order",      # classical sort or fair-sharing tournament setup
    "cycle.admit",      # sequential admit loop (assume/apply/requeue)
    "cycle.admit.prepare",            # per-head assignments, speculative
                                      # admit objects: overlaps the scan
    "cycle.admit.fetch",              # blocking wait for the admit scan
    "cycle.admit.apply",              # admit, issue preemptions, skip
    "cycle.admit.requeue",            # requeue of every head not assumed
    "burst",            # one whole schedule_burst call; parent of the
                        # burst.* phases and of its per-cycle cycles
    "burst.pack",       # burst-window pack (streaming, or full)
    "burst.pack.drain",               # journal drain + round-trip checks
    "burst.pack.walk",                # stage A: per-queue row records
    "burst.pack.grid",                # stage B: the dense [C, M] planes
    "burst.pack.grid.patch",          # delta: row write, orders, cells
    "burst.pack.grid.snapshot",       # host copy of the planes a plan owns
    "burst.dispatch",   # fused-kernel launch incl. sharded shard launches
    "burst.dispatch.tighten",         # dtype narrowing on the host
    "burst.dispatch.scatter",         # resident rows: gather, send, update
    "burst.dispatch.launch",          # the fused kernel's (async) jit call
    "burst.fetch",      # decision-plane fetch (flags + full planes)
    "burst.apply",      # host apply of one modeled burst cycle
    "burst.callbacks",  # the caller's on_cycle_start / on_cycle hooks
    "boundary",         # finish_workloads: quota release, removals, wake-up
    "host.collect",     # the driver's own collection of the young
                        # generations where a scheduling section closes
    "host.gc",          # one collection of the cyclic collector, nested
                        # in whatever span was open (gc.callbacks)
    "wal.append",       # one journal op append
    "wal.commit",       # cycle-boundary commit (group commit included)
    "wal.compact",      # checkpoint + tail rewrite
    "fed.sync",         # one federation reconcile/sync step
    "svc.cycle",        # one whole service step (drain + K inner cycles)
    "svc.ingest",       # cycle-boundary drain of the service ingest queue
    "svc.shutdown",     # graceful-drain epilogue (final WAL/journal flush)
)

#: Roster suffix of a phase's self time (duration less recorded children).
SELF_SUFFIX = ".self"


@dataclass(slots=True)
class SpanRecord:
    """One finished span."""
    name: str
    t0: float           # wall clock at entry (perf_counter seconds)
    dur: float          # wall-clock duration, seconds
    depth: int          # nesting depth at entry (0 = top level)
    parent: str         # name of the enclosing span ("" at top level)
    vt: float           # virtual-clock reading at entry (0.0 if none)


class Span:
    """A single open span; re-usable only after it closed.

    The tracer pools one instance per nesting depth — LIFO pairing
    means the slot for the current depth is always closed when
    ``span()`` hands it out again, so the steady-state hot path
    allocates no span objects at all."""

    __slots__ = ("tracer", "name", "t0", "depth", "parent", "vt",
                 "child_s", "_note", "_open")

    def __init__(self, tracer: "Tracer", name: str = ""):
        self.tracer = tracer
        self.name = name
        self._open = False

    def __enter__(self) -> "Span":
        if self._open:
            raise RuntimeError(f"span {self.name!r} entered twice")
        st = self.tracer._stack
        self.depth = len(st)
        self.parent = st[-1].name if st else ""
        self.vt = self.tracer.vclock() if self.tracer.vclock else 0.0
        self.child_s = None     # summed recorded children; None = none
        st.append(self)
        self._open = True
        self._note = self.tracer._annotation(self.name)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        st = self.tracer._stack
        if not self._open or not st or st[-1] is not self:
            raise RuntimeError(
                f"span {self.name!r} closed out of order "
                f"(stack: {[s.name for s in st]})")
        dur = time.perf_counter() - self.t0
        self._note.__exit__(exc_type, exc, tb)
        st.pop()
        self._open = False
        if st:
            up = st[-1]
            up.child_s = dur if up.child_s is None else up.child_s + dur
        self.tracer._finish(self, dur)
        return False            # never swallow the exception


class _CountedSpan:
    """Histogram-only leaf span: times every entry into the phase
    histogram but skips the stack, parent/depth bookkeeping, the
    virtual-clock read, the profiler annotation, and the retained
    record (so it never counts as a child in a parent's self time).
    By contract counted spans are leaves and must not nest inside one
    another (each tracer reuses a single instance per depth-free
    site)."""

    __slots__ = ("tracer", "name", "t0")

    def __init__(self, tracer: "Tracer"):
        self.tracer = tracer
        self.name = ""

    def __enter__(self) -> "_CountedSpan":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.perf_counter() - self.t0
        tr = self.tracer
        tr.finished_total += 1
        tr._observe(self.name, dur)
        return False            # never swallow the exception


class _NoopSpan:
    """Shared do-nothing span: what ``span(...)`` hands out when
    tracing is off.  A single module-level instance — entering it
    allocates nothing and touches no clock."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOOP = _NoopSpan()


class Tracer:
    """Collects spans into per-phase histograms + a per-cycle buffer.

    ``registry`` receives the ``kueue_span_duration_seconds`` series;
    ``vclock`` is an optional side-effect-free callable returning the
    scenario's virtual time (the driver's ``clock``).  The tracer keeps
    every finished span of the *current* cycle in ``cycle_spans`` until
    the flight recorder drains it; total counts survive draining."""

    def __init__(self, registry: Optional[Registry] = None,
                 vclock: Optional[Callable[[], float]] = None):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.registry = registry if registry is not None else Registry()
        self.vclock = vclock
        # the tracer's span stack belongs to the thread that built it
        # (the driver coordinator); spans opened from host-pool worker
        # threads are handed the no-op — their work is timed inside
        # the coordinator's enclosing span, and a shared LIFO stack
        # cannot absorb concurrent closes
        self._owner = threading.get_ident()
        self._stack: list[Span] = []
        self._pool: list[Span] = []      # one reusable span per depth
        self._counted = _CountedSpan(self)   # shared histogram-only leaf
        self._hists: dict[str, Histogram] = {}   # phase -> registry hist
        # the collector's span (``_on_gc``) has a slot of its own: a
        # collection can start between any two bytecodes, those of
        # ``span()`` and ``Span.__exit__`` included, where the pooled
        # span of that depth is still somebody's.  Its series is made
        # here and not at a first observation, which would insert into
        # the registry from inside whatever the collection interrupted
        self._gc_span = Span(self, "host.gc")
        self._hist_for("host.gc")
        self.cycle_spans: list[SpanRecord] = []
        self.finished_total = 0
        self.opened_total = 0
        # retained finished spans for /debug/spans: the first
        # trace_capacity are kept, the rest counted in dropped_total
        self.trace_spans: list[SpanRecord] = []
        self.trace_capacity = 65536
        self.dropped_total = 0

    def span(self, name: str, counted: bool = False):
        self.opened_total += 1
        if counted:
            s = self._counted
            s.name = name
            return s
        pool = self._pool
        d = len(self._stack)
        if d >= len(pool):
            pool.append(Span(self))
        s = pool[d]
        if s._open:     # a held handle mid-misuse: never rename it
            s = Span(self)
        s.name = name
        return s

    def _hist_for(self, name: str) -> Histogram:
        # same series/key shape Registry.observe would create, the
        # dict probes amortised away from the per-span path; the
        # first-insert holds the registry lock so a concurrent
        # /metrics render never sees the dict resize mid-iteration
        key = ("kueue_span_duration_seconds", name)
        with self.registry._lock:
            h = self.registry.histograms.get(key)
            if h is None:
                h = Histogram(buckets=SPAN_BUCKETS)
                self.registry.histograms[key] = h
        self._hists[name] = h
        return h

    def _finish(self, s: Span, dur: float) -> None:
        self.finished_total += 1
        rec = SpanRecord(s.name, s.t0, dur, s.depth, s.parent, s.vt)
        self.cycle_spans.append(rec)
        if len(self.trace_spans) < self.trace_capacity:
            self.trace_spans.append(rec)
        else:
            self.dropped_total += 1
        self._observe(s.name, dur)
        if s.child_s is not None:
            self._observe(s.name + SELF_SUFFIX, max(0.0, dur - s.child_s))

    def _observe(self, phase: str, seconds: float) -> None:
        h = self._hists.get(phase)
        if h is None:
            h = self._hist_for(phase)
        h.observe(seconds)

    def drain_cycle(self) -> list[SpanRecord]:
        out, self.cycle_spans = self.cycle_spans, []
        return out

    def open_spans(self) -> list[str]:
        return [s.name for s in self._stack]

    # -- reporting -----------------------------------------------------

    def roster(self) -> dict[str, dict]:
        """Per-phase count/p50/p99 from the registry histograms, for
        artifacts and the flight-recorder dump."""
        out: dict[str, dict] = {}
        for key, h in sorted(self.registry.histograms.items()):
            if key[0] != "kueue_span_duration_seconds":
                continue
            phase = key[1]
            out[phase] = {
                "count": h.n,
                "p50_ms": h.quantile(0.5) * 1000.0,
                "p99_ms": h.quantile(0.99) * 1000.0,
                "total_s": h.total,
            }
        return out


#: The process-wide tracer every span site consults.  None = off.
ACTIVE: Optional[Tracer] = None


def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` entry, there while a tracer is installed:
    one collection of the cyclic collector is one ``host.gc`` span,
    opened at ``"start"`` and closed at ``"stop"`` inside whatever span
    was open, so a parent's self time is free of the collector's
    pauses.  The callback runs on the thread that triggered the
    collection; on any thread but the tracer's owner it does nothing,
    as ``span()``.  Collections do not nest and between the two calls
    run only finalizers, so the pair is LIFO on the owner's stack."""
    t = ACTIVE
    if t is None or threading.get_ident() != t._owner:
        return
    s = t._gc_span
    if phase == "start":
        if not s._open:
            t.opened_total += 1
            s.__enter__()
    elif s._open:
        s.__exit__(None, None, None)


def install(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Make ``tracer`` the process's tracer (None: tracing off).  The
    collector's callback is in ``gc.callbacks`` exactly while one is
    installed: with tracing off the list is as the interpreter left
    it."""
    global ACTIVE
    ACTIVE = tracer
    hooked = _on_gc in gc.callbacks
    if tracer is not None and not hooked:
        gc.callbacks.append(_on_gc)
    elif tracer is None and hooked:
        gc.callbacks.remove(_on_gc)
    return tracer


def clear() -> None:
    install(None)


def span(name: str, counted: bool = False):
    """The one instrumentation entry point: a context manager that is
    a real span when tracing is on and the shared no-op otherwise.

    ``counted=True`` marks an ultra-hot leaf (per-op WAL appends: the
    operation itself is ~2µs, so a retained record would out-cost it):
    every entry is still timed into the phase histogram — roster
    counts and percentiles stay exact — but no SpanRecord lands in the
    cycle buffer or the Chrome trace.

    Calls from a thread other than the tracer's owner (host-pool
    workers fanning WAL segment commits or pack-walk partitions) get
    the no-op: the shared LIFO span stack is single-threaded by
    design, and pooled work is already timed by the coordinator's
    enclosing span."""
    t = ACTIVE
    if t is None or threading.get_ident() != t._owner:
        return _NOOP
    return t.span(name, counted)


def to_chrome_trace(spans) -> dict:
    """Chrome trace-event JSON (the ``chrome://tracing`` / Perfetto
    format): one complete ("X") event per finished span, microsecond
    wall-clock timestamps, virtual time and depth in ``args``."""
    events = []
    for s in spans:
        events.append({
            "name": s.name,
            "ph": "X",
            "ts": s.t0 * 1e6,
            "dur": s.dur * 1e6,
            "pid": 1,
            "tid": 1,
            "args": {"virtual_time": s.vt, "depth": s.depth,
                     "parent": s.parent},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
