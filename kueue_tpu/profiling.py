"""jax.profiler integration: the operator's entry to an on-demand trace.

SURVEY §5.1: the reference's observability is zap logging + a pprof flag
on the perf harness; the TPU-native equivalent is a jax.profiler trace
with one StepTraceAnnotation per scheduling cycle, so device dispatches
(admit scans, preemption searches) line up under named cycle steps in
TensorBoard/Perfetto.

Named host phases are not this module's: they are the spans of
``obs/trace.py``, each of which holds a ``jax.profiler.TraceAnnotation``
while the tracer is on.  Turn the tracer on (``KUEUE_TPU_OBS_TRACE=1``
or ``ObsPlane.enable_tracing``) together with a trace and
``burst.pack``, ``cycle.nominate.search_launch`` and the rest sit on the
trace's host plane beside the device's operations, on one clock.

Usage: ``start_trace(logdir)`` / ``stop_trace()`` around any driver
activity, or ``cli schedule --profile-dir`` / ``cli serve
--profile-dir`` (traced until SIGTERM).  ``cycle_step`` is a no-op until
a trace is active.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

_active = threading.Event()


def start_trace(logdir: str) -> None:
    """Begin a jax.profiler trace (host + device activity) to logdir."""
    import jax
    jax.profiler.start_trace(logdir)
    _active.set()


def stop_trace() -> None:
    import jax
    if _active.is_set():
        _active.clear()
        jax.profiler.stop_trace()


def trace_active() -> bool:
    return _active.is_set()


@contextlib.contextmanager
def trace(logdir: Optional[str]):
    """start_trace/stop_trace as a context; no-op when logdir is None."""
    if not logdir:
        yield
        return
    start_trace(logdir)
    try:
        yield
    finally:
        stop_trace()


@contextlib.contextmanager
def cycle_step(cycle: int):
    """Mark one scheduling cycle as a profiler step (the step markers
    SURVEY §5.1 names as the TPU equivalent of per-cycle logging)."""
    if not _active.is_set():
        yield
        return
    import jax
    with jax.profiler.StepTraceAnnotation("schedule_cycle",
                                          step_num=cycle):
        yield
