"""Observability plane: span pairing, flight-recorder parity under
chaos, tracing bit-identity, and strict Prometheus exposition.

The tentpole guarantees under test:

- spans pair exactly — every opened span closes exactly once, LIFO,
  even when exceptions unwind through arbitrary nesting; misuse
  (double close, out-of-order close) fails loudly;
- tracing changes no decision — a traced run and an untraced run of
  the same scenario produce bit-identical per-cycle decision batches
  and final workload state;
- the flight recorder survives chaos — digests recorded before an
  injected crash match the fault-free control arm, a dump mid-crash
  state works, and an ``obs.dump`` crash mid-dump cannot corrupt the
  ring (the re-dump is byte-identical);
- ``Registry.render()`` speaks real Prometheus text exposition —
  checked by a strict parser, escaping round-trip included.
"""

from __future__ import annotations

import io
import json
import os
import random
import re
import signal
import urllib.request

import pytest

from kueue_tpu.chaos import injector as chaos
from kueue_tpu.chaos.injector import ChaosInjector, InjectedCrash
from kueue_tpu.controller.driver import Driver
from kueue_tpu.debugger import Dumper, dump_state
from kueue_tpu.metrics import Registry, SERIES
from kueue_tpu.obs import EventStream, FlightRecorder, ObsPlane
from kueue_tpu.obs import trace as trace_mod
from kueue_tpu.obs.flight import decision_digest
from kueue_tpu.obs.trace import (
    HOT_PATH_PHASES,
    SELF_SUFFIX,
    Tracer,
    _NOOP,
    span,
    to_chrome_trace,
)
from kueue_tpu.utils.journal import CycleWAL
from kueue_tpu.visibility import VisibilityServer

from test_burst import (
    add_workloads,
    build,
    mk,
    preempting_cluster,
    run_burst,
    run_host,
    simple_cluster,
)
from test_chaos_recovery import (
    drain_spec,
    full_state,
    recover,
    resume_host,
    run_host_until_crash,
)


@pytest.fixture(autouse=True)
def _obs_off():
    """Neither the tracer nor chaos may leak between tests."""
    trace_mod.clear()
    chaos.clear()
    yield
    trace_mod.clear()
    chaos.clear()


# ---------------------------------------------------------------------------
# Span pairing
# ---------------------------------------------------------------------------

def test_span_off_is_shared_noop():
    """Tracing off: span() hands out one module-level singleton — no
    allocation, no clock read, nothing to balance."""
    assert trace_mod.ACTIVE is None
    assert span("cycle") is _NOOP
    assert span("wal.append") is _NOOP
    with span("cycle"):
        with span("cycle.admit"):
            pass


def test_span_nesting_records_depth_and_parent():
    t = Tracer()
    with t.span("cycle"):
        with t.span("cycle.admit"):
            with t.span("wal.append"):
                pass
    recs = t.drain_cycle()
    assert [r.name for r in recs] == ["wal.append", "cycle.admit", "cycle"]
    by_name = {r.name: r for r in recs}
    assert by_name["cycle"].depth == 0 and by_name["cycle"].parent == ""
    assert by_name["cycle.admit"].parent == "cycle"
    assert by_name["wal.append"].depth == 2
    assert t.open_spans() == []


def test_span_pairing_property_under_forced_exceptions():
    """Property: however exceptions unwind through nested spans, every
    opened span closes exactly once and the stack drains to empty."""
    t = Tracer()
    rng = random.Random(1234)

    class Boom(Exception):
        pass

    def descend(depth):
        with t.span(f"phase.{depth}"):
            if rng.random() < 0.25:
                raise Boom()
            for _ in range(rng.randrange(3)):
                descend(depth + 1)

    for _ in range(200):
        try:
            descend(0)
        except Boom:
            pass
        assert t.open_spans() == [], "exception left a span open"
    assert t.opened_total == t.finished_total > 0
    assert len(t.drain_cycle()) == t.finished_total


def test_span_misuse_fails_loudly():
    t = Tracer()
    s = t.span("cycle")
    with pytest.raises(RuntimeError, match="closed out of order"):
        s.__exit__(None, None, None)          # never entered
    a = t.span("a").__enter__()
    b = t.span("b").__enter__()
    with pytest.raises(RuntimeError, match="out of order"):
        a.__exit__(None, None, None)          # b still open above it
    b.__exit__(None, None, None)
    a.__exit__(None, None, None)
    with pytest.raises(RuntimeError, match="out of order"):
        a.__exit__(None, None, None)          # double close
    with pytest.raises(RuntimeError, match="entered twice"):
        a.__enter__()
        a.__enter__()


def test_span_never_swallows_exceptions():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("cycle"):
            raise ValueError("boom")
    assert t.open_spans() == []


def test_chrome_trace_shape():
    t = Tracer(vclock=lambda: 42.0)
    with t.span("cycle"):
        with t.span("cycle.admit"):
            pass
    doc = to_chrome_trace(t.trace_spans)
    assert doc["displayTimeUnit"] == "ms"
    assert [e["name"] for e in doc["traceEvents"]] \
        == ["cycle.admit", "cycle"]
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0.0
        assert e["args"]["virtual_time"] == 42.0
    json.dumps(doc)   # must be serializable as-is


# ---------------------------------------------------------------------------
# Event stream
# ---------------------------------------------------------------------------

def test_event_stream_bounded_with_exact_totals():
    es = EventStream(capacity=4)
    seen = []
    es.subscribe(lambda ev: seen.append(ev.key))
    for i in range(7):
        es.emit("admit", f"ns/w{i}", cluster_queue="cq", reason="Quota")
    assert es.total == 7 and es.dropped == 3
    assert [e.key for e in es.tail()] == [f"ns/w{i}" for i in range(3, 7)]
    assert seen == [f"ns/w{i}" for i in range(7)]
    rep = es.report()
    assert rep["counts"] == {"admit": 7}
    assert rep["buffered"] == 4 and rep["dropped"] == 3


# ---------------------------------------------------------------------------
# Decision bit-identity: tracing on vs off
# ---------------------------------------------------------------------------

def test_tracing_on_vs_off_is_bit_identical():
    """The acceptance bar: the traced arm's per-cycle decision batches
    and final durable state match the untraced arm exactly."""
    spec = drain_spec()
    dc, cc = build(spec)
    control = run_host(dc, cc, 12, 2)

    dt, ct = build(spec)
    tracer = dt.obs.enable_tracing()
    traced = run_host(dt, ct, 12, 2)
    dt.obs.disable_tracing()

    for k, (x, y) in enumerate(zip(traced, control)):
        assert decision_digest(x) == decision_digest(y), f"cycle {k}"
    assert dt.admitted_keys() == dc.admitted_keys()
    assert full_state(dt) == full_state(dc)
    # and the traced arm actually traced the hot path (device-solver
    # cycles skip the classical cycle.order stage; see the WAL test for
    # the classical path)
    phases = set(tracer.roster())
    assert {"cycle", "cycle.snapshot", "cycle.nominate",
            "cycle.admit"} <= phases
    assert {p.removesuffix(SELF_SUFFIX) for p in phases} \
        <= set(HOT_PATH_PHASES)
    # empty cycles (no queue heads) return before the span opens
    assert 1 <= tracer.roster()["cycle"]["count"] <= 12


def test_traced_wal_spans_and_flight_ring(tmp_path):
    """WAL append/commit spans land, and each applied cycle's record
    carries that cycle's drained spans."""
    d, c = build(add_workloads(simple_cluster(),
                               [mk(f"w{i}", "lq-0-0", 1000, t=float(i + 1))
                                for i in range(6)]),
                 use_device=False)
    d.attach_wal(CycleWAL(str(tmp_path / "wal.jsonl")))
    tracer = d.obs.enable_tracing()
    run_host(d, c, 4, 0)
    assert {"cycle.order", "wal.append", "wal.commit"} \
        <= set(tracer.roster())
    assert d.obs.flight.recorded_total == 4
    for rec in d.obs.flight.ring:
        names = {s.name for s in rec.spans}
        assert "cycle" in names, "cycle record missing its own spans"
    # the flight recorder drains the buffer with each cycle; the last
    # call's own collection closed after its record and waits there
    assert [s.name for s in tracer.cycle_spans] == ["host.gc", "host.collect"]


# ---------------------------------------------------------------------------
# Sub-phases, self time, the drop counter, one clock with the profiler
# ---------------------------------------------------------------------------

NEW_PHASES = (
    "cycle.nominate.classify", "cycle.nominate.walk",
    "cycle.nominate.candidates", "cycle.nominate.search_pack",
    "cycle.nominate.search_launch", "cycle.nominate.search_decode",
    "cycle.nominate.search_fallback", "cycle.nominate.scan_dispatch",
    "cycle.admit.fetch",
    "burst.pack.drain", "burst.pack.walk", "burst.pack.grid",
    "burst.dispatch.tighten", "burst.dispatch.launch",
)


def run_preempting(traced, small_k=False):
    """One per-cycle cycle in which three heads search for targets,
    then a fused burst of three; ``small_k`` shrinks the batched
    search's top rung under the heads' four candidates, so the cycle
    takes the one-launch-a-head route.  Returns (driver, tracer, the
    four cycles' stats)."""
    from kueue_tpu.ops import preemption_solver
    d, clock = preempting_cluster()
    tracer = d.obs.enable_tracing() if traced else None
    with pytest.MonkeyPatch.context() as mp:
        if small_k:
            mp.setattr(preemption_solver, "K_LADDER", (2,))
        out = run_host(d, clock, 1, 0) + run_burst(d, clock, 3, 0)
    d.obs.disable_tracing()
    return d, tracer, out


@pytest.fixture(scope="module")
def preempting_spans():
    """Every span record of the batched and of the fallback arm."""
    recs = []
    for small_k in (False, True):
        recs += run_preempting(True, small_k)[1].trace_spans
    trace_mod.clear()
    return recs


@pytest.mark.parametrize("phase", NEW_PHASES)
def test_sub_phase_is_listed_emitted_and_parented(phase, preempting_spans):
    assert phase in HOT_PATH_PHASES
    parents = {r.parent for r in preempting_spans if r.name == phase}
    assert parents == {phase.rsplit(".", 1)[0]}, \
        f"{phase}: never emitted, or under {parents}"


def test_self_time_is_parent_less_recorded_children():
    t = Tracer()
    with t.span("cycle"):
        with t.span("cycle.nominate"):
            with t.span("wal.append", counted=True):   # not a child
                pass
        with t.span("cycle.admit"):
            pass
    by = {r.name: r for r in t.trace_spans}
    roster = t.roster()
    assert roster["cycle" + SELF_SUFFIX]["total_s"] == pytest.approx(
        by["cycle"].dur - by["cycle.nominate"].dur - by["cycle.admit"].dur,
        abs=1e-9)
    assert roster["cycle" + SELF_SUFFIX]["count"] == 1
    # no recorded child, no self series; and .self is never a record
    assert "cycle.nominate" + SELF_SUFFIX not in roster
    assert "cycle.admit" + SELF_SUFFIX not in roster
    assert all(not r.name.endswith(SELF_SUFFIX) for r in t.trace_spans)
    # the pooled span forgets its children when it is handed out again
    with t.span("cycle"):
        pass
    assert t.roster()["cycle" + SELF_SUFFIX]["count"] == 1


def test_trace_spans_keeps_the_first_and_counts_the_rest():
    d, c = build(simple_cluster())
    t = d.obs.enable_tracing()
    t.trace_capacity = 3
    for i in range(5):
        with span(f"phase.{i}"):
            pass
    assert [r.name for r in t.trace_spans] == ["phase.0", "phase.1",
                                               "phase.2"]
    assert t.dropped_total == 2 and t.finished_total == 5
    assert d.obs.report()["spans_dropped"] == 2
    assert len(t.drain_cycle()) == 5    # the flight recorder loses none


def test_tracing_on_vs_off_is_bit_identical_when_preempting():
    """The same bar on a cycle that searches for targets, by both
    search routes, and on the burst after it."""
    for small_k in (False, True):
        dc, _, control = run_preempting(False, small_k)
        dt, tracer, traced = run_preempting(True, small_k)
        assert any(s.preempted_targets for s in control)
        for k, (x, y) in enumerate(zip(traced, control, strict=True)):
            assert decision_digest(x) == decision_digest(y), f"cycle {k}"
        assert full_state(dt) == full_state(dc)
        assert dt.scheduler.preemptor.stats == dc.scheduler.preemptor.stats
        assert ("cycle.nominate.search_fallback" in tracer.roster()) \
            is small_k


def test_profiler_trace_holds_the_spans_as_host_events(tmp_path):
    """One system: with the tracer on, a profiler session that anyone
    started shows the spans on its host plane, on its own clock."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, tracer, _ = run_preempting(True)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    events = {}
    for line in host[0].lines:
        for ev in line.events:
            events.setdefault(ev.name, []).append(ev.duration_ns)
    for name in ("cycle.nominate", "cycle.nominate.search_launch",
                 "burst.pack.grid"):
        recs = [r for r in tracer.trace_spans if r.name == name]
        assert len(events.get(name, ())) == len(recs) >= 1, name
    # the annotation is held for the span's lifetime: same duration
    rec = next(r for r in tracer.trace_spans if r.name == "cycle.nominate")
    assert events["cycle.nominate"][0] == pytest.approx(rec.dur * 1e9,
                                                        abs=1e6)


# ---------------------------------------------------------------------------
# Flight recorder under chaos
# ---------------------------------------------------------------------------

def test_flight_digests_match_control_up_to_the_crash(tmp_path):
    """Crash with the admit op journaled but unapplied: every cycle the
    crashed arm recorded carries the same decision digest as the
    fault-free control, and the crashed recorder still dumps cleanly."""
    spec, cluster = drain_spec(), simple_cluster()
    dc, cc = build(spec)
    run_host(dc, cc, 12, 2)
    control_digests = [r.digest for r in dc.obs.flight.ring]

    d1, c1 = build(spec)
    d1.attach_wal(CycleWAL(str(tmp_path / "wal.jsonl")))
    chaos.install(ChaosInjector(seed=3)).arm("wal.admit", at=5)
    out, crashed = run_host_until_crash(d1, c1, 12, 2)
    assert crashed
    chaos.clear()

    crashed_digests = [r.digest for r in d1.obs.flight.ring]
    assert len(crashed_digests) == len(out) < 12
    assert crashed_digests == control_digests[:len(out)]

    dump = d1.obs.flight.dump()
    assert dump["buffered"] == len(out)
    assert [c["digest"] for c in dump["cycles"]] == crashed_digests
    # the recorded cycles all completed BEFORE the 5th (fatal) hit
    assert 0 < dump["cycles"][-1]["chaos_hits"].get("wal.admit", 0) < 5

    # recovery produces a working driver with a fresh recorder that
    # keeps recording from the re-run cycle on
    tail_admits = {op["key"] for op in d1._wal.tail if op["op"] == "admit"}
    d2 = recover(cluster, d1, d1._wal)
    k = len(out)
    resume_host(d2, c1, k + 1, 2, out, tick_first=False)
    # fold the WAL-replayed admits back into the re-run cycle's record
    # so the modeled-runtime finisher sees the full decision batch
    out[k].admitted.extend(sorted(tail_admits))
    resume_host(d2, c1, 12, 2, out)
    assert d2.obs.flight.recorded_total == 12 - k
    assert d2.admitted_keys() == dc.admitted_keys()


def test_obs_dump_crashpoint_cannot_corrupt_recorder():
    """The ``obs.dump`` site fires after the ring snapshot, before
    serialization: a crash mid-dump leaves the recorder untouched and
    the re-dump byte-identical to an undisturbed dump."""
    d, c = build(add_workloads(simple_cluster(),
                               [mk(f"w{i}", "lq-0-0", 1000, t=float(i + 1))
                                for i in range(8)]))
    run_host(d, c, 5, 2)
    before = d.obs.flight.dump()
    dumps_before = d.obs.flight.dumps

    chaos.install(ChaosInjector(seed=7)).arm("obs.dump", at=1)
    with pytest.raises(InjectedCrash):
        d.obs.flight.dump()
    assert d.obs.flight.dumps == dumps_before, \
        "a crashed dump must not count as completed"

    after = d.obs.flight.dump()   # fault exhausted (times=1)
    chaos.clear()
    # chaos_hits snapshots differ once an injector is installed; the
    # ring payload itself must be identical
    strip = lambda doc: json.dumps(
        {**doc, "cycles": [{k: v for k, v in cyc.items()
                            if k != "chaos_hits"} for cyc in doc["cycles"]]},
        sort_keys=True)
    assert strip(after) == strip(before)
    assert d.obs.flight.recorded_total == before["recorded_total"]


def test_flight_ring_is_bounded():
    fr = FlightRecorder(capacity=3)
    from kueue_tpu.scheduler.scheduler import CycleStats
    for i in range(10):
        fr.record(CycleStats(cycle=i, admitted=[f"ns/w{i}"]))
    assert fr.recorded_total == 10
    assert [r.cycle for r in fr.ring] == [7, 8, 9]
    assert fr.dump()["buffered"] == 3
    assert fr.dump(tail=2)["cycles"][0]["cycle"] == 8


# ---------------------------------------------------------------------------
# ObsPlane integration on the driver
# ---------------------------------------------------------------------------

def test_driver_emits_events_and_obs_block():
    d, c = build(add_workloads(simple_cluster(),
                               [mk(f"w{i}", "lq-0-0", 1000, t=float(i + 1))
                                for i in range(6)]))
    out = run_host(d, c, 4, 1)
    admits = sum(len(s.admitted) for s in out)
    assert d.obs.events.counts["admit"] == admits > 0
    ev = d.obs.events.tail(1)[0]
    assert ev.reason == "QuotaReserved" and ev.cluster_queue
    assert ev.cycle > 0 and ev.vt > 0.0

    block = d.stats["obs"]
    assert block["events"]["counts"]["admit"] == admits
    assert block["flight"]["recorded_total"] == 4
    assert block["tracing"] is False

    d.refresh_resource_metrics()
    text = d.metrics.render()
    assert f'kueue_obs_events_total{{kind="admit"}} {admits}' in text
    assert "kueue_flight_cycles_recorded 4" in text


def test_eviction_emits_evict_and_requeue_events():
    from kueue_tpu.controller.driver import WaitForPodsReadyConfig
    from tests.conftest import FakeClock
    clock = FakeClock()
    d = Driver(clock=clock, wait_for_pods_ready=WaitForPodsReadyConfig(
        enable=True, timeout_seconds=30.0,
        requeuing_backoff_base_seconds=10,
        requeuing_backoff_max_seconds=100))
    simple_cluster(n_cohorts=1, cqs=1)(d)
    d.create_workload(mk("slow", "lq-0-0", 1000, t=1.0))
    d.run_until_settled()
    clock.tick(31.0)
    d.evict_for_pods_ready_timeout("default/slow")
    kinds = [e.kind for e in d.obs.events.tail()]
    assert "evict" in kinds and "requeue" in kinds
    evict = next(e for e in d.obs.events.tail() if e.kind == "evict")
    assert evict.key == "default/slow"
    assert evict.reason == "PodsReadyTimeout"


def test_obs_env_flags_configure_the_plane(monkeypatch):
    monkeypatch.setenv("KUEUE_TPU_OBS_TRACE", "1")
    monkeypatch.setenv("KUEUE_TPU_FLIGHT_CYCLES", "17")
    monkeypatch.setenv("KUEUE_TPU_OBS_EVENTS", "33")
    from tests.conftest import FakeClock
    d = Driver(clock=FakeClock())
    assert d.obs.tracing is True
    assert d.obs.flight.capacity == 17
    assert d.obs.events.capacity == 33
    trace_mod.clear()


# ---------------------------------------------------------------------------
# Dump surfaces: SIGUSR2 + HTTP
# ---------------------------------------------------------------------------

def test_dump_state_carries_obs_sections(tmp_path):
    d, c = build(add_workloads(simple_cluster(),
                               [mk(f"w{i}", "lq-0-0", 1000, t=float(i + 1))
                                for i in range(6)]))
    d.attach_wal(CycleWAL(str(tmp_path / "wal.jsonl")))
    d.obs.enable_tracing()
    run_host(d, c, 3, 0)
    text = dump_state(d)
    assert "-- in-flight cycle --" in text
    assert "-- flight recorder" in text
    assert "digest=" in text and "spans=" in text
    assert "-- events --" in text and "'admit'" in text
    assert "-- wal --" in text
    assert "open spans: []" in text


def test_sigusr2_triggers_a_dump():
    d, c = build(add_workloads(simple_cluster(),
                               [mk("w0", "lq-0-0", 1000, t=1.0)]))
    run_host(d, c, 2, 0)
    buf = io.StringIO()
    old = signal.getsignal(signal.SIGUSR2)
    try:
        Dumper(d, out=buf).listen_for_signal()
        os.kill(os.getpid(), signal.SIGUSR2)
    finally:
        signal.signal(signal.SIGUSR2, old)
    text = buf.getvalue()
    assert "=== kueue-tpu state dump ===" in text
    assert "-- flight recorder" in text


def test_http_debug_endpoints():
    d, c = build(add_workloads(simple_cluster(),
                               [mk(f"w{i}", "lq-0-0", 1000, t=float(i + 1))
                                for i in range(4)]))
    d.obs.enable_tracing()
    run_host(d, c, 3, 0)
    server = VisibilityServer(d)
    port = server.start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return r.read().decode()

        fr = json.loads(get("/debug/flightrecorder"))
        assert fr["buffered"] == 3 and fr["tracing"] is True
        assert fr["events"]["counts"].get("admit", 0) > 0
        assert all(c["digest"] for c in fr["cycles"])

        tr = json.loads(get("/debug/spans"))
        names = {e["name"] for e in tr["traceEvents"]}
        assert "cycle" in names and names <= set(HOT_PATH_PHASES)

        assert "# TYPE kueue_span_duration_seconds histogram" \
            in get("/metrics")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Strict Prometheus text exposition
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\",?)*)\})?"
    r" (-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|\+Inf|-Inf|NaN)$")
_LABEL_RE = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)=\"((?:[^\"\\]|\\.)*)\"")


def _unescape(v: str) -> str:
    out, i = [], 0
    while i < len(v):
        if v[i] == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            out.append({"n": "\n", "\\": "\\", '"': '"'}.get(nxt, nxt))
            i += 2
        else:
            out.append(v[i])
            i += 1
    return "".join(out)


def parse_prometheus(text: str) -> dict:
    """Strict exposition-format parser: enforces HELP/TYPE headers per
    family, sample-name/family agreement, cumulative histogram buckets
    ending in +Inf, and bucket/count consistency.  Returns
    ``{(name, ((label, value), ...)): float}``."""
    assert text.endswith("\n"), "exposition must end with a newline"
    samples: dict = {}
    helps: dict = {}
    types: dict = {}
    family = None
    for line in text.splitlines():
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            assert _NAME_RE.match(name), name
            assert name not in helps, f"duplicate HELP for {name}"
            helps[name] = help_text
            family = name
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert name == family, "TYPE must follow its HELP"
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), kind
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        sname, labelstr, value = m.groups()
        assert family is not None and family in types, \
            f"sample {sname} before any TYPE header"
        kind = types[family]
        if kind == "histogram":
            assert sname in (f"{family}_bucket", f"{family}_sum",
                             f"{family}_count"), \
                f"{sname} does not belong to histogram {family}"
        else:
            assert sname == family, \
                f"{sname} under family {family}"
        labels = tuple((k, _unescape(v))
                       for k, v in _LABEL_RE.findall(labelstr or ""))
        key = (sname, labels)
        assert key not in samples, f"duplicate series {key}"
        samples[key] = float(value)
        if kind == "counter":
            assert samples[key] >= 0.0, f"negative counter {key}"
    # histogram invariants, per label set
    for name, kind in types.items():
        if kind != "histogram":
            continue
        series = {}
        for (sname, labels), v in samples.items():
            if sname == f"{name}_bucket":
                base = tuple(kv for kv in labels if kv[0] != "le")
                le = dict(labels)["le"]
                series.setdefault(base, []).append((le, v))
        for base, buckets in series.items():
            values = [v for _, v in buckets]
            assert values == sorted(values), \
                f"{name}{base}: buckets not cumulative"
            assert buckets[-1][0] == "+Inf", f"{name}{base}: no +Inf"
            count = samples[(f"{name}_count", base)]
            assert buckets[-1][1] == count, \
                f"{name}{base}: +Inf bucket != _count"
            assert (f"{name}_sum", base) in samples
    return samples


def test_render_round_trips_through_strict_parser():
    d, c = build(drain_spec())
    d.obs.enable_tracing()
    run_host(d, c, 8, 2)
    d.refresh_resource_metrics()
    text = d.metrics.render()
    samples = parse_prometheus(text)
    assert samples, "no samples rendered"
    families = {n for n, _ in samples}
    assert any(f.startswith("kueue_span_duration_seconds") for f in families)
    assert ("kueue_admission_attempts_total", (("result", "success"),)) \
        in samples
    # every rendered family that is a kueue_* series must be declared
    bases = {re.sub(r"_(bucket|sum|count)$", "", f)
             if any(f == n + s for n in SERIES
                    for s in ("_bucket", "_sum", "_count")) else f
             for f in families}
    assert all(b in SERIES for b in bases if b.startswith("kueue_")), \
        sorted(b for b in bases if b.startswith("kueue_")
               and b not in SERIES)


def test_render_escapes_labels_round_trip():
    r = Registry()
    hairy = 'cq"quoted\\slash\nnewline'
    r.inc("kueue_evicted_workloads_total", (hairy, "Preempted"))
    r.observe("kueue_admission_wait_time_seconds", (hairy,), 3.0)
    samples = parse_prometheus(r.render())
    assert samples[("kueue_evicted_workloads_total",
                    (("cluster_queue", hairy),
                     ("reason", "Preempted")))] == 1.0
    assert samples[("kueue_admission_wait_time_seconds_count",
                    (("cluster_queue", hairy),))] == 1.0


def test_render_declares_help_and_type_for_every_family():
    d, c = build(drain_spec())
    run_host(d, c, 4, 0)
    d.refresh_resource_metrics()
    text = d.metrics.render()
    sample_names = set()
    for line in text.splitlines():
        if not line.startswith("#"):
            sample_names.add(_SAMPLE_RE.match(line).group(1))
    helped = {l.split(" ", 3)[2] for l in text.splitlines()
              if l.startswith("# HELP ")}
    for n in sample_names:
        base = re.sub(r"_(bucket|sum|count)$", "", n)
        assert n in helped or base in helped, f"{n} has no HELP"


def test_validator_phases_are_a_subset_of_hot_path():
    """validate_artifacts._OBS_HOST_PHASES (what the OBS artifact's
    roster must cover) must name real tracer phases — a rename in
    HOT_PATH_PHASES that leaves the validator behind fails here, not
    in a soak run."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "scripts"))
    try:
        import validate_artifacts
        assert set(validate_artifacts._OBS_HOST_PHASES) <= \
            set(HOT_PATH_PHASES)
    finally:
        sys.path.pop(0)
