"""The deployment kind ``tas_blocks``: its plain reference against the
program's host scalar scheduler and against the device path (every
head's placement from the batched topology stage), its controls, its
ledger, its traffic's records, the roofline reader of the stage's
kernel, and its sizes from the configuration's file alone.

Three witnesses on one cluster, same finishes, same clock: the plain
reference (which imports nothing of the program), the host scheduler
(``FlavorAssigner``'s TAS hook and the scalar tree walk) and the device
path.  Every cycle's admitted, evicted, skipped and preempting sets and
every admission's nodes have to agree.
"""

import ast
import copy
import json
import os

import numpy as np
import pytest

import correct
import harness
import peaks
from deployment_kinds import tas_blocks as kind
from deployment_kinds.tas_blocks import cluster, program, reference
from metric_kinds import tas_roofline
from traffic_kinds import burst_rounds_tas

from conftest import BENCH, HERE, ROOT
from helpers import hand_plan

TOY = os.path.join(HERE, "data", "toy-tas49k.json")
CONFIG = os.path.join(BENCH, "configs", "mk8-1kcq-tas49k.json")
FIRST = os.path.join(BENCH, "configs", "mk8-1kcq-zipf.json")
CELL = "tas49k.backlog"
NEW_METRICS = {
    # name: (layer, moves, source)
    "tas_ms": ("scheduler", "cycle_ms", "program_span"),
    "tas_queries_per_round": ("scheduler", "cycle_ms", "program_counter"),
    "tas_host_queries_per_round": ("scheduler", "cycle_ms",
                                   "program_counter"),
    "tas_topology_nofit_per_round": ("scheduler", "cycle_ms",
                                     "program_counter"),
    "tas_refit_skips_per_round": ("scheduler", "cycle_ms",
                                  "program_counter"),
    "tas_kernel_ms": ("kernels", "cycle_ms", "device_trace"),
    "tas_kernel_roofline": ("kernels", "cycle_ms", "device_trace"),
}


def traffic_params(**over):
    with open(os.path.join(BENCH, "traffic", "backlog-tas.json")) as f:
        return dict(json.load(f), **over)


def witness(plan, rounds, seed, use_device=False, **over):
    """``rounds`` rounds of the cell's traffic through the host scalar
    scheduler or the device path, and the comparison's verdict."""
    driver, clock = program.build_driver(plan, use_device=use_device)
    if use_device:
        program.warm_up(driver, plan)
    traffic = burst_rounds_tas.Traffic(traffic_params(**over), plan, seed)
    records = [traffic.round(driver, clock) for _ in range(rounds)]
    return records, correct.compare(kind, plan, records, 0), driver


@pytest.fixture(autouse=True)
def _gate_off_after():
    yield
    from kueue_tpu import features
    features.set_feature_gates({"TopologyAwareScheduling": False})


@pytest.fixture(scope="module")
def toy_run():
    plan = cluster.plan_cluster(harness.load_config(TOY), 5)
    records, verdict, _ = witness(plan, 8, 5,
                                  finish_fraction_per_round=0.05)
    return plan, records, verdict


def _numbers(verdict):
    return {k: v["value"] for k, v in verdict["compared"].items()}


@pytest.mark.parametrize("seed", [1, 2, 3, 2_147_483_659])
def test_reference_equals_scalar_scheduler(seed):
    plan = cluster.plan_cluster(harness.load_config(TOY), seed)
    records, verdict, _ = witness(plan, 6, seed,
                                  finish_fraction_per_round=0.05)
    assert verdict["correct"], (_numbers(verdict),
                                verdict["facts"]["first_mismatch"])
    assert sum(len(c.admitted) for r in records for c in r.cycles) > 0
    assert sum(len(c.skipped) for r in records for c in r.cycles) > 0


@pytest.mark.parametrize("seed", [4, 2_147_483_700])
def test_device_path_equals_reference_and_scalar_scheduler(seed):
    plan = cluster.plan_cluster(harness.load_config(TOY), seed)
    records, verdict, driver = witness(plan, 6, seed, use_device=True,
                                       finish_fraction_per_round=0.05)
    assert verdict["correct"], (_numbers(verdict),
                                verdict["facts"]["first_mismatch"])
    host, _, _ = witness(plan, 6, seed, finish_fraction_per_round=0.05)
    for a, b in zip(records, host):
        for x, y in zip(a.cycles, b.cycles):
            for name in kind.COMPARED:
                assert sorted(getattr(x, name)) == sorted(getattr(y, name))
    stats = driver.scheduler.solver.stats
    assert stats["scalar_heads"] == stats["cq_shape_heads"] == 0, stats
    assert stats["tas_host_queries"] == 0, stats
    assert stats["tas_queries"] >= stats["tas_heads"] > 0, stats
    assert stats["tas_refit_skips"] > 0, stats


@pytest.mark.parametrize("broken", kind.CONTROLS)
def test_control_fails_the_comparison(toy_run, broken):
    import control
    plan, records, verdict = toy_run
    assert verdict["correct"]
    readings = control.control_readings(kind, plan, records, 0)
    assert not readings[broken]["correct"], readings[broken]
    assert readings[broken]["mismatched_cycles"] > 0


def _required_rack_plan():
    """One block of two racks of two 3,000 m nodes; each rack has one
    full node and one with 2,500 m free.  A 2-pod gang of 2,500 m a pod
    that requires a rack fits no rack, and would fit the block."""
    base = hand_plan(
        [("cq-a", "co", {"cpu": 20_000, "memory": 100},
          {"cpu": 0, "memory": 0})],
        [("cq-a", "r-full-0", 50, {"cpu": 3000, "memory": 1}, 1, 10),
         ("cq-a", "r-full-1", 50, {"cpu": 3000, "memory": 1}, 2, 10),
         ("cq-a", "r-half-0", 50, {"cpu": 500, "memory": 1}, 3, 10),
         ("cq-a", "r-half-1", 50, {"cpu": 500, "memory": 1}, 4, 10),
         ("cq-a", "gang", 100, {"cpu": 5000, "memory": 2}, 5, None)])
    base.wl_pods = np.array([1, 1, 1, 1, 2])
    base.config["deployment"]["topology"] = {"name": "racks"}
    fields = {k: getattr(base, k) for k in base.__dataclass_fields__}
    plan = cluster.TASPlan(
        **fields, levels=["block", "rack", "host"], fanout=(1, 2, 2),
        node_cap=np.array([3000, 8 << 30, 10], dtype=np.int64),
        wl_topology=np.array([0, 0, 0, 0, cluster.REQUIRED], dtype=np.int8),
        wl_level=np.array([-1, -1, -1, -1, 1], dtype=np.int8))
    # hosts 1 and 3 full, hosts 0 and 2 with 2,500 m free
    plan.place_start = np.array([0, 1, 2, 3, 4, 4])
    plan.place_host = np.array([1, 3, 0, 2], dtype=np.int32)
    plan.place_pods = np.array([1, 1, 1, 1], dtype=np.int32)
    return plan


def test_required_as_preferred_fails_the_cluster_written_out():
    """The TOY_CONTROLS control: at the cell's size every required rack
    holds its gang, so relaxing the requirement decides nothing there
    (PERF.md section 7); on this cluster it does."""
    plan = _required_rack_plan()
    good = reference.Reference(plan)
    bad = reference.Reference(plan, broken="required_as_preferred")
    r_good, r_bad = good.cycle(1001.0), bad.cycle(1001.0)
    assert r_good.admitted == [] and r_good.inadmissible == ["default/gang"]
    assert r_bad.admitted == ["default/gang"]
    assert sorted(r_bad.placed) == [
        "default/gang@main=block-00/rack-00-00/node-00-00-00x1",
        "default/gang@main=block-00/rack-00-01/node-00-01-00x1"]
    assert "required_as_preferred" in reference.TOY_CONTROLS


def test_the_program_refuses_the_split_and_reserves():
    """The host scheduler on the same cluster: quota fits, no rack holds
    the gang, the empty tree does, so the head is Preempt with nothing
    to preempt and reserves; nothing is admitted."""
    from traffic_kinds.burst_rounds import RoundRecord
    plan = _required_rack_plan()
    for use_device in (False, True):
        driver, clock = program.build_driver(plan, use_device=use_device)
        traffic = burst_rounds_tas.Traffic(
            traffic_params(finish_fraction_per_round=0.0), plan, 1)
        rec = traffic.round(driver, clock, max_cycles=2)
        assert rec.cycles[0].admitted == [] and rec.cycles[0].placed == []
        v = correct.compare(kind, plan, [rec], 0)
        assert v["compared"]["mismatched_cycles"]["value"] == 0
        assert isinstance(rec, RoundRecord)


def test_ledger_counts_an_overcommitted_node_and_a_split(toy_run):
    plan, records, _ = toy_run
    led = kind.ledger(plan, records)
    assert led["node_overcommits"] == led["required_split"] == 0
    assert led["quota_violations"] == 0
    broken = copy.deepcopy(records)
    # a full node gets one more pod of a pending workload
    full = int(plan.place_host[0])
    path = "/".join(plan.host_values(full))
    pending = np.flatnonzero(~plan.wl_running)[:40].tolist()
    cyc = broken[0].cycles[0]
    cyc.admitted = cyc.admitted + [plan.key(i) for i in pending]
    cyc.placed = cyc.placed + [
        f"{plan.key(i)}@main={path}x{int(plan.wl_pods[i])}" for i in pending]
    led = kind.ledger(plan, broken)
    assert led["node_overcommits"] >= 1
    assert led["quota_violations"] >= led["node_overcommits"]
    # a required gang whose pods sit in two racks
    from traffic_kinds.burst_rounds import CycleRecord, RoundRecord
    plan = _required_rack_plan()
    cyc = CycleRecord(clock=1001.0, admitted=["default/gang"], evicted=[],
                      skipped=[], preempting=[], heads=1, seconds=0.0)
    cyc.placed = [f"default/gang@main={'/'.join(plan.host_values(h))}x1"
                  for h in (0, 2)]
    led = kind.ledger(plan, [RoundRecord(finished=[], cycles=[cyc])])
    assert led["required_split"] == 1 and led["node_overcommits"] == 0
    assert led["quota_violations"] == 1


def test_a_program_without_the_tas_counter_is_turned_away(monkeypatch):
    """The commit before the deployment landed walks every head on the
    host; the kind ends its run before set-up, with an exit code other
    than 0."""
    from kueue_tpu.ops.solver import CycleSolver
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    real = CycleSolver.__init__

    def without_counter(self, *a, **kw):
        real(self, *a, **kw)
        del self.stats[program.TAS_COUNTER]
    monkeypatch.setattr(CycleSolver, "__init__", without_counter)
    with pytest.raises(SystemExit) as stop:
        program.build_driver(plan)
    assert stop.value.code not in (0, None)
    assert program.TAS_COUNTER in str(stop.value.code)
    monkeypatch.undo()
    driver, _ = program.build_driver(plan)
    assert driver.scheduler.solver.stats[program.TAS_COUNTER] == 0


def test_the_reference_imports_nothing_of_the_program():
    """Neither the reference, nor the cluster plan, nor the ledger."""
    here = os.path.dirname(reference.__file__)
    for name in ("reference.py", "cluster.py", "ledger.py"):
        with open(os.path.join(here, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n.split(".")[0] in ("kueue_tpu", "jax")
                           for n in names), (name, names)


def test_the_traffic_records_every_admissions_nodes(toy_run):
    plan, records, _ = toy_run
    for rnd in records:
        for cyc in rnd.cycles:
            keys = {p.split("@")[0] for p in cyc.placed}
            assert keys == set(cyc.admitted)
            for p in cyc.placed:
                where = p.split("=", 1)[1]
                assert where.count("/") == 2 and "x" in where


def test_the_roofline_counts_the_leaf_plane_once_a_launch():
    problem = {"tas_leaves": 49_152, "tas_resources": 3}
    plane = 49_152 * 3 * peaks.WORD
    assert tas_roofline.least_bytes(problem, 1, 0) == plane
    assert tas_roofline.least_bytes(problem, 2, 10) == (
        2 * plane + 10 * (6 + 2) * peaks.WORD)
    spec = {"programs": ["jit_place_queries"],
            "launch_counter": "tas_launches",
            "query_counter": "tas_unique_queries"}
    # the distinct questions are counted, not every one asked
    ctx = {"trace": {"program_s": {"jit_place_queries": 0.01}},
           "problem": dict(problem), "device_kind": "TPU v5 lite",
           "counters": {"traced": {"tas_launches": 4, "tas_queries": 900,
                                   "tas_unique_queries": 36}}}
    got = tas_roofline.read(spec, ctx)
    least = tas_roofline.least_bytes(problem, 4, 36) / 819e9
    assert got == pytest.approx(100.0 * least / 0.01)
    # a program with no such launches, or a kind with no leaves: nothing
    ctx["counters"]["traced"] = {}
    assert tas_roofline.read(spec, ctx) is None
    ctx["counters"]["traced"] = {"tas_launches": 4}
    ctx["problem"] = {"real_rows": 1}
    assert tas_roofline.read(spec, ctx) is None


# ---- the configuration and the plan at the cell's size --------------------

def test_the_configuration_is_the_first_ones_population_on_nodes():
    cfg = harness.load_config(CONFIG)
    first = harness.load_config(FIRST)
    for key in ("population", "clock", "fused_path_limits"):
        assert cfg[key] == first[key], key
    for c, f in zip(cfg["classes"], first["classes"]):
        assert {k: v for k, v in c.items() if k != "topology"} == f
    dep, fdep = cfg["deployment"], first["deployment"]
    for key in ("cluster_queues", "cohorts", "resources", "resource_units",
                "queueing_strategy", "preemption", "quota_rule",
                "quota_round_up", "borrowing_limit"):
        assert dep[key] == fdep[key], key
    topo = dep["topology"]
    assert topo["blocks"] * topo["racks_per_block"] \
        * topo["hosts_per_rack"] == 49_152
    assert topo["levels"] == ["cloud.provider.com/topology-block",
                              "cloud.provider.com/topology-rack",
                              "kubernetes.io/hostname"]
    assert cfg["reduced"] == [] and cfg["kind"] == "tas_blocks"
    assert len(cfg["source"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    (entry,) = [c for c in m["configs"] if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    (cell,) = [w for w in m["workloads"] if w["config"] == cfg["name"]]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert cell["traffic"] == "backlog-tas"
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name, (layer, moves, source) in NEW_METRICS.items():
        e = by_name[name]
        assert (e["layer"], e["moves"], e["source"]) == (layer, moves,
                                                         source)
        assert e["workloads"] == [CELL]
    # the guards that every head stays on the vector path
    for name in ("scalar_heads_per_round", "cq_shape_heads_per_round",
                 "compiles_in_window", "device_peak_gib"):
        assert CELL in by_name[name]["workloads"], name


def test_the_plan_at_the_cells_size():
    """Every running workload placed, no node over its allocatable, a
    required-rack gang inside one rack, and the fill of the config's
    notes."""
    cfg = harness.load_config(CONFIG)
    plan = cluster.plan_cluster(cfg, 2_147_483_700)
    rows = cluster.queue_rows(cfg)
    assert rows["M"] == 65_536
    run = np.flatnonzero(plan.wl_running)
    assert len(run) == int(sum(rows["running"]))
    counts = np.diff(plan.place_start)
    assert (counts[run] > 0).all() and (counts[~plan.wl_running] == 0).all()
    per = plan.wl_request // plan.wl_pods[:, None]
    row_of = np.repeat(np.arange(len(plan.wl_queue)), counts)
    used = np.zeros((plan.n_hosts, 3), dtype=np.int64)
    np.add.at(used, plan.place_host,
              np.column_stack([per[row_of], np.ones(len(row_of), np.int64)])
              * plan.place_pods[:, None])
    assert (used <= plan.node_cap).all()
    fill = used.sum(0) / (plan.node_cap * plan.n_hosts)
    assert 0.7 < fill[0] < 0.9 and 0.7 < fill[1] < 0.85
    sums = np.bincount(row_of, weights=plan.place_pods,
                       minlength=len(plan.wl_queue))
    assert (sums[run] == plan.wl_pods[run]).all()
    racks = plan.place_host // plan.fanout[2]
    for i in run[plan.wl_topology[run] == cluster.REQUIRED][:5000]:
        a, b = plan.place_start[i], plan.place_start[i + 1]
        assert len(set(racks[a:b].tolist())) == 1
    problem = kind.problem(cfg, plan)
    assert problem["tas_leaves"] == 49_152 and problem["tas_resources"] == 3
    assert problem["tas_domains"] == [48, 1536, 49_152]
