"""Every metric's data file against the program it reads: a span metric
names a phase the tracer lists, a counter metric a key the program's
stats dicts start with (or one the harness counts itself).  A rename in
the program then fails here and not by a metric that reads nothing on
the chip."""

import functools
import glob
import json
import os

import pytest

from conftest import BENCH

SPECS = {}
for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.json"))):
    with open(path) as f:
        spec = json.load(f)
    SPECS[spec["name"]] = spec

# what benchmarks/harness.py:CompileCounter.read adds to the counters
HARNESS_COUNTERS = {"programs_built", "programs_loaded_from_cache",
                    "programs_compiled"}


@functools.cache
def program_counter_keys():
    """The keys ``harness.program_counters`` finds on a driver that has
    not run yet: what the stats dicts are initialised with."""
    from kueue_tpu.ops.burst import BurstSolver
    from kueue_tpu.ops.solver import CycleSolver
    from kueue_tpu.scheduler.preemption import Preemptor
    keys = set()
    for stats in (BurstSolver().stats, CycleSolver().stats,
                  Preemptor().stats):
        keys |= {k for k, v in stats.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
    return frozenset(keys)


def test_every_manifest_metric_has_a_data_file_and_the_reverse():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert names == set(SPECS)


@pytest.mark.parametrize("name", sorted(
    n for n, s in SPECS.items() if s["kind"] == "span"))
def test_span_metric_names_a_listed_phase(name):
    from kueue_tpu.obs.trace import HOT_PATH_PHASES, SELF_SUFFIX
    phase = SPECS[name]["span"]
    assert phase.removesuffix(SELF_SUFFIX) in HOT_PATH_PHASES
    if phase.endswith(SELF_SUFFIX):
        # a self series exists only when the phase had a child: the
        # metric has to read 0, not nothing, when it had none
        assert SPECS[name].get("zero_when_absent") is True


@pytest.mark.parametrize("name", sorted(
    n for n, s in SPECS.items()
    if s["kind"] in ("counter", "ratio") or "launch_counter" in s))
def test_counter_metric_names_an_initialised_counter(name):
    spec = SPECS[name]
    keys = (spec.get("counters", []) + spec.get("over", [])
            + spec.get("under", []))
    if "launch_counter" in spec:
        keys.append(spec["launch_counter"])
    assert keys
    have = program_counter_keys() | HARNESS_COUNTERS
    assert set(keys) <= have, sorted(set(keys) - have)


def test_ratio_reader():
    from metric_kinds import ratio
    spec = {"over": ["a"], "under": ["b", "c"]}
    ctx = {"counters": {"window": {"a": 3, "b": 8, "c": 4}}}
    assert ratio.read(spec, ctx) == 75.0
    ctx["counters"]["window"].update(b=0, c=0)
    assert ratio.read(spec, ctx) is None          # nothing was launched
    del ctx["counters"]["window"]["a"]
    assert ratio.read(spec, ctx) is None          # a program without it


def test_decide_roofline_reader():
    """The least time to decide the traced cycles over the device's busy
    time, every program counted: 6 cycles x (322,000 rows x 25 B + 1,000
    queues x 24 B) at 819 GB/s over 5.8 s busy."""
    from metric_kinds import trace
    spec = SPECS["decide_roofline"]
    ctx = {"trace": {"busy_s": 5.8, "window_s": 36.0, "program_s": {}},
           "traced_cycles": 6, "device_kind": "TPU v5 lite",
           "problem": {"real_rows": 322_000, "queues": 1000,
                       "resources": 2},
           "counters": {"traced": {}}}
    least_s = 6 * (322_000 * 25 + 1000 * 24) / 819e9
    assert trace.read(spec, ctx) == pytest.approx(100 * least_s / 5.8)
    assert 0.0009 < trace.read(spec, ctx) < 0.0011
    # no launch of any one program is needed: the kernel's own share
    # falls silent here, this one does not
    assert trace.read(SPECS["burst_kernel_roofline"], ctx) is None
    # twice the busy time for the same decisions is half the share
    ctx["trace"]["busy_s"] = 11.6
    assert trace.read(spec, ctx) == pytest.approx(50 * least_s / 5.8)
    for nothing in ({"traced_cycles": 0}, {"trace": None},
                    {"trace": dict(ctx["trace"], busy_s=0.0)}):
        assert trace.read(spec, dict(ctx, **nothing)) is None
