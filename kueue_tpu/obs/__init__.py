"""Live telemetry plane: span tracing, flight recorder, event stream.

The operator-facing observability layer the reference ships as
``pkg/metrics`` + ``pkg/visibility`` + ``pkg/debugger`` + Events,
reproduced for the solver stack:

- :mod:`trace`  — the one structured span tracer over the admission
  hot path (schedule phases and the sub-phases of nominate, burst
  pack/dispatch/fetch/apply and the sub-phases of pack and dispatch,
  WAL, federation sync), off by default and zero-allocation when off.
  On, every recorded span is also a ``jax.profiler.TraceAnnotation``,
  so a profiler trace (``profiling.py``) shows it beside the device's
  operations on one clock; a parent with children reports its self
  time as ``<name>.self``; the cyclic collector's runs are ``host.gc``
  spans (a ``gc.callbacks`` entry that is there only while tracing is
  on); spans ``/debug/spans`` had no room for are counted
  (``spans_dropped`` in the ``obs`` block), never lost silently;
- :mod:`flight` — ring-buffer flight recorder of the last N cycles
  (decision digests, spans, chaos hits), dumpable on demand, over
  HTTP, and on SIGUSR2;
- :mod:`events` — bounded subscribable admit/evict/preempt/requeue/
  eject stream feeding the recorder and every soak artifact's ``obs``
  block.

:class:`ObsPlane` is the per-driver composition: the driver owns one,
emits events through it, records each applied cycle into it, and the
telemetry endpoints (``visibility.VisibilityServer``) and the SIGUSR2
dumper (``debugger``) read from it.  Guarantees, test-enforced:
decisions are bit-identical with tracing on vs off, and the traced
north-star p50 stays within 5% of untraced (OBS artifact).
"""

from __future__ import annotations

from typing import Optional

from . import events as _events
from . import flight as _flight
from . import trace as _trace
from .events import Event, EventStream            # noqa: F401
from .flight import CycleRecord, FlightRecorder   # noqa: F401
from .trace import (                               # noqa: F401
    HOT_PATH_PHASES,
    SELF_SUFFIX,
    SPAN_BUCKETS,
    SpanRecord,
    Tracer,
    span,
    to_chrome_trace,
)


class ObsPlane:
    """One driver's observability state: event stream + flight recorder
    + (optional) tracing enablement.  Always attached — emitting an
    event or recording a cycle is a deque append and never reads state
    the scheduler writes mid-cycle — while tracing stays opt-in."""

    def __init__(self, driver, flight_cycles: int = 256,
                 event_capacity: int = 4096):
        self.driver = driver
        self.events = EventStream(capacity=event_capacity)
        self.flight = FlightRecorder(capacity=flight_cycles)
        self.tracer: Optional[Tracer] = None   # last tracer enabled here
        self._last_recorded = None   # identity of the last CycleStats

    @classmethod
    def from_env(cls, driver) -> "ObsPlane":
        from ..features import env_int, env_value
        plane = cls(driver,
                    flight_cycles=env_int("KUEUE_TPU_FLIGHT_CYCLES"),
                    event_capacity=env_int("KUEUE_TPU_OBS_EVENTS"))
        if env_value("KUEUE_TPU_OBS_TRACE") not in ("", "0"):
            plane.enable_tracing()
        return plane

    # -- tracing lifecycle ---------------------------------------------

    def enable_tracing(self) -> Tracer:
        """Install the process tracer bound to this driver's registry
        and (virtual) clock, and with it the collector's callback
        (``trace.install``).  Idempotent per driver."""
        t = _trace.ACTIVE
        if t is None or t.registry is not self.driver.metrics:
            t = _trace.install(Tracer(registry=self.driver.metrics,
                                      vclock=self.driver.clock))
        self.tracer = t
        return t

    def disable_tracing(self) -> None:
        _trace.clear()

    @property
    def tracing(self) -> bool:
        return _trace.ACTIVE is not None

    # -- emission ------------------------------------------------------

    def emit(self, kind: str, key: str, cluster_queue: str = "",
             reason: str = "", note: str = "") -> Event:
        d = self.driver
        return self.events.emit(
            kind, key, cluster_queue=cluster_queue, reason=reason,
            note=note, cycle=d.scheduler.scheduling_cycle,
            vt=d.clock())

    def record_cycle(self, stats) -> None:
        """Record one applied cycle into the flight recorder.  Deduped
        by stats identity: the burst path funnels both normal and
        modeled cycles through ``finish_cycle`` while the normal path
        records inside ``schedule_once`` — the same batch must land in
        the ring exactly once."""
        if stats is self._last_recorded:
            return
        self._last_recorded = stats
        t = _trace.ACTIVE
        spans = t.drain_cycle() if t is not None else ()
        self.flight.record(stats, vt=self.driver.clock(), spans=spans,
                           events_total=self.events.total)

    # -- reporting -----------------------------------------------------

    def _tracer_view(self) -> Optional[Tracer]:
        """The tracer whose data belongs to this driver: the installed
        one when it is ours, else the last one enabled here — so the
        endpoints keep serving spans after a harness uninstalls the
        process-global between cycles."""
        t = _trace.ACTIVE
        if t is not None and t.registry is self.driver.metrics:
            return t
        return self.tracer

    def spans_chrome_trace(self) -> dict:
        t = self._tracer_view()
        return to_chrome_trace(t.trace_spans if t is not None else ())

    def report(self) -> dict:
        """The ``obs`` block every soak artifact carries from r16 on."""
        out = {
            "events": self.events.report(),
            "flight": {
                "capacity": self.flight.capacity,
                "recorded_total": self.flight.recorded_total,
                "buffered": len(self.flight.ring),
                "dumps": self.flight.dumps,
            },
            "tracing": self.tracing,
        }
        t = self._tracer_view()
        if t is not None:
            out["spans"] = t.roster()
            out["spans_dropped"] = t.dropped_total
        return out
