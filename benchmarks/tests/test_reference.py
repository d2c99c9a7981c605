"""The plain reference against the program's host scalar scheduler.

The scalar scheduler (``Driver(use_device_solver=False)``) is the
second witness: same cluster, same finishes, same clock, and every
cycle's admitted, evicted, skipped and preempting sets have to agree,
on the configuration's policies.  Three cases are written out by hand:
memory and not cpu refuses a head; a cold queue reclaims from a
borrower; an evicted workload is admitted again inside one round.
"""

import os

import pytest

import correct
import harness
from deployment_kinds import flat_one_flavor as kind
from deployment_kinds.flat_one_flavor import cluster, program, reference

from conftest import HERE
from helpers import drive, hand_plan, short

TOYS = ["toy-zipf.json"]


def witness(plan, rounds, **over):
    driver, clock = program.build_driver(plan, use_device=False)
    records = drive(driver, clock, plan, rounds, **over)
    verdict = correct.compare(kind, plan, records, 0)
    return records, verdict


@pytest.mark.parametrize("toy", TOYS)
@pytest.mark.parametrize("seed", [3, 4, 2_147_483_659])
def test_reference_equals_scalar_scheduler(toy, seed):
    cfg = harness.load_config(os.path.join(HERE, "data", toy))
    plan = cluster.plan_cluster(cfg, seed)
    records, verdict = witness(plan, 6, seed=seed,
                               finish_fraction_per_round=0.05)
    assert verdict["correct"], verdict
    facts = verdict["facts"]
    assert facts["cycles_compared"] == 48
    assert facts["cycles_with_evictions"] > 5
    assert facts["cross_queue_evictions"] > 0
    assert sum(len(c.admitted) for r in records for c in r.cycles) > 40


@pytest.mark.parametrize("toy", TOYS)
def test_control_fails_the_comparison(toy):
    """The control is the reference with one stated guarantee switched
    off, put in the program's place: it has to come out not correct."""
    cfg = harness.load_config(os.path.join(HERE, "data", toy))
    plan = cluster.plan_cluster(cfg, 5)
    records, verdict = witness(plan, 4, seed=5,
                               finish_fraction_per_round=0.05)
    assert verdict["correct"]
    for broken in reference.CONTROLS:
        control = correct.compare(kind, plan, records, 0, broken=broken)
        assert not control["correct"], broken
        assert control["compared"]["mismatched_cycles"]["value"] >= 3


def test_control_readings_put_each_control_in_the_programs_place():
    """``control.py``'s own loop: the control decides the recorded
    cycles, its answers take the record's place field by field of
    ``COMPARED``, and the same comparison reads them; the record itself
    is left as the program wrote it."""
    import control
    cfg = harness.load_config(os.path.join(HERE, "data", TOYS[0]))
    plan = cluster.plan_cluster(cfg, 6)
    records, verdict = witness(plan, 4, seed=6,
                               finish_fraction_per_round=0.05)
    readings = control.control_readings(kind, plan, records, 0)
    assert set(readings) == set(kind.CONTROLS)
    for row in readings.values():
        assert row["correct"] is False and row["mismatched_cycles"] >= 3
    assert correct.compare(kind, plan, records, 0)["correct"]


ONE_QUEUE = [("cq-0", "cohort-0", {"cpu": 10_000, "memory": 8},
              {"cpu": 0, "memory": 0})]


def test_memory_and_not_cpu_refuses_a_head():
    plan = hand_plan(ONE_QUEUE, [
        ("cq-0", "running", 50, {"cpu": 1000, "memory": 6}, 1, 500),
        ("cq-0", "head", 50, {"cpu": 1000, "memory": 4}, 2, None),
        ("cq-0", "next", 50, {"cpu": 1000, "memory": 2}, 3, None)])
    records, verdict = witness(plan, 1, finish_fraction_per_round=0.0,
                               cycles_per_round=3)
    assert verdict["correct"], verdict
    cycles = records[0].cycles
    # 9,000 m of cpu are free; 2 GiB of memory are not enough for 4
    assert cycles[0].admitted == [] and cycles[0].heads == 1
    # BestEffortFIFO parks the head and the next one, which fits, goes in
    assert [short(k) for k in cycles[1].admitted] == ["next"]
    # with memory unenforced the head would have been admitted at once
    ref = reference.Reference(plan, broken="memory_unenforced")
    assert [short(k) for k in ref.cycle(1001.0).admitted] == ["head"]


TWO_QUEUES = [
    ("cq-0", "cohort-0", {"cpu": 4000, "memory": 32},
     {"cpu": 4000, "memory": 32}),
    ("cq-1", "cohort-0", {"cpu": 4000, "memory": 32},
     {"cpu": 4000, "memory": 32})]


def test_cold_queue_reclaims_from_a_borrower():
    plan = hand_plan(TWO_QUEUES, [
        ("cq-0", "a-old", 100, {"cpu": 2000, "memory": 2}, 1, 501),
        ("cq-0", "a-mid", 50, {"cpu": 2000, "memory": 2}, 2, 502),
        ("cq-0", "a-new", 50, {"cpu": 2000, "memory": 2}, 3, 503),
        ("cq-1", "cold", 50, {"cpu": 4000, "memory": 4}, 4, None)])
    records, verdict = witness(plan, 1, finish_fraction_per_round=0.0,
                               cycles_per_round=3)
    assert verdict["correct"], verdict
    c0, c1 = records[0].cycles[:2]
    # cq-0 borrows 2,000 m; the cold head is under its nominal and takes
    # it back: lower priority first, then the newest reservation
    assert [short(k) for k in c0.preempting] == ["cold"]
    assert [short(k) for k in c0.evicted] == ["a-new"]
    assert verdict["facts"]["cross_queue_evictions"] == 1
    # the evicted workload is back in cq-0's queue, but the cohort is
    # full once the cold head is in
    assert [short(k) for k in c1.admitted] == ["cold"]
    assert [short(k) for k in c1.skipped] == ["a-new"]


def test_evicted_workload_is_admitted_again_inside_a_round():
    """Kueue's flapping, in one ``schedule_burst``: the cold queue's
    second workload reclaims more than it needs; the eviction wakes the
    queue's parked, older and larger first workload, which takes the
    head and still does not fit; so the evicted workload, back at the
    head of its own queue, is admitted again before the preemptor."""
    plan = hand_plan(TWO_QUEUES, [
        ("cq-0", "filler", 100, {"cpu": 4000, "memory": 4}, 1, 501),
        ("cq-0", "victim", 50, {"cpu": 2000, "memory": 2}, 2, 502),
        ("cq-1", "too-big", 50, {"cpu": 5000, "memory": 5}, 3, None),
        ("cq-1", "preemptor", 50, {"cpu": 2500, "memory": 2}, 4, None)])
    # cohort 8,000 m: cq-0 runs 6,000 (borrows 2,000); 2,000 are free
    records, verdict = witness(plan, 1, finish_fraction_per_round=0.0,
                               cycles_per_round=6)
    assert verdict["correct"], verdict
    events = [(i, kind, short(k)) for i, c in enumerate(records[0].cycles)
              for kind, keys in (("evict", c.evicted), ("admit", c.admitted),
                                 ("preempting", c.preempting))
              for k in keys]
    # cycle 0: too-big is over its queue's nominal, cannot preempt, parks
    assert records[0].cycles[0].admitted == []
    assert events[:3] == [(1, "evict", "victim"),
                          (1, "preempting", "preemptor"),
                          (2, "admit", "victim")], events
    # and round it goes: the preemptor is the head again
    assert (3, "evict", "victim") in events
