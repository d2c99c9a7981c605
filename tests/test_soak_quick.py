"""Seconds-level smoke of the two soak entry points (satellite of the
open-loop traffic PR): ``--quick`` must stay wired, exit clean, and
emit schema-valid artifacts.  Marked ``slow`` — these spawn real soak
subprocesses (~1-2 min each) and belong to the soak tier, not tier-1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_quick(script, out_path, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", script),
         "--quick", "--out", out_path, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"{script} --quick failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    with open(out_path) as f:
        return json.load(f)


def _validate(out_path):
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import validate_artifacts
        return validate_artifacts.validate(out_path)
    finally:
        sys.path.pop(0)


def test_traffic_soak_quick(tmp_path):
    out = str(tmp_path / "TRAFFIC_r99.json")
    d = _run_quick("traffic_soak.py", out, extra=("--shards", "2"))
    assert d["quick"] is True
    assert d["replay_identical"] is True
    assert d["serial_shard_decisions_match"] is True
    assert d["control"]["interleaved"] is True
    assert _validate(out) == []


def test_northstar_hetero_quick(tmp_path):
    """The heterogeneous fast path end to end at smoke scale: in-kernel
    fungibility burst arm + 2-shard arm + host oracle, interleaved, with
    a schema-valid 'hetero' block."""
    out = str(tmp_path / "NORTHSTAR_r99.json")
    d = _run_quick("northstar_e2e.py", out, extra=(
        "--burst", "--ab-hetero", "--flavors", "4", "--resources", "3",
        "--ab-shards", "2"))
    assert d["quick"] is True
    h = d["hetero"]
    assert h["decisions_identical_across_arms"] is True
    assert h["zero_host_fallbacks"] is True
    assert h["fallbacks"]["burst_dirty_scalar"] == 0
    assert h["drift"]["environment_drift"]["interleaved"] is True
    assert _validate(out) == []


def test_scale_soak_quick(tmp_path):
    """The scale ceiling end to end at smoke scale: streaming vs
    rebuild vs classic (all r18 optimizations off) arms on the same
    state up to 4k CQs, bit-identical planes + decisions at every
    probed size, the row-ceiling probe, the heap/WAL-shard benches,
    the residue ledger, and a completed mini-soak with the sharded
    group-committed WAL attached."""
    out = str(tmp_path / "SCALE_r99.json")
    d = _run_quick("scale_soak.py", out,
                   extra=("--soak-workloads", "20000"))
    assert d["quick"] is True
    assert d["sizes"] == [1000, 4000]
    assert d["parity"]["planes_identical_all"] is True
    assert d["parity"]["decisions_identical_all"] is True
    # every r18 optimization off must still be bit-identical
    assert d["parity"]["decisions_identical_classic_all"] is True
    # r19: the single-flag bulk-apply arm (only KUEUE_TPU_CYCLE_BULK_APPLY
    # flipped) is the honest A/B denominator and may never change a decision
    assert d["parity"]["decisions_identical_nobulk_all"] is True
    assert d["parity"]["max_res_ts_equal_all"] is True
    assert d["soak"]["completed"] is True
    assert d["soak"]["wal"]["wal_commits"] > 0
    # group commit: strictly fewer fsyncs than commits
    assert d["soak"]["wal"]["wal_fsyncs"] < d["soak"]["wal"]["wal_commits"]
    # the soak WAL runs sharded by default from r18 on
    assert d["soak"]["wal"]["layout"] == "sharded"
    assert d["soak"]["wal"]["wal_shards"] >= 2
    assert d["control"]["interleaved"] is True
    # streaming must already beat the rebuild arm at 4k CQs
    assert d["curve"][-1]["pack_speedup"] > 1.0
    # aggregate compression shrinks the packed planes (admitted rows
    # of the non-preempting soak cluster fold into aggregates)
    assert d["aggregate"]["max_res_ts_equal_all"] is True
    top = d["aggregate"]["points"][-1]
    assert top["rows_packed"] < top["rows_row_backed"]
    assert d["ceiling"]["rows_packed"] <= d["ceiling"]["rows_row_backed"]
    assert d["heap"]["microbench"]["order_parity"] is True
    assert d["wal_shard"]["replay_parity"] is True
    # r19: the single-appender sharded WAL auto-collapses to one hot
    # segment; registered appenders re-engage striping
    assert d["wal_shard"]["collapsed_segments"] == 1
    assert d["wal_shard"]["striped_segments"] >= 2
    # r19: head-only packing — the ceiling universe packs into a row
    # *budget* charged only to preempting-forest rows
    assert d["ceiling"]["active_cqs_pending"] >= d["ceiling"]["cqs"]
    assert d["ceiling"]["rows_packed"] <= d["ceiling"]["row_budget"]
    assert d["head_pack"]["budget_rows"] <= d["head_pack"]["grid_rows"]
    assert d["head_pack"]["flag"] == "KUEUE_TPU_HEAD_PACK"
    # r19: the pooled host apply/pack plane never changes a decision,
    # and the pooled WAL-commit plane preserves total seq order
    assert d["host_pool"]["decisions_identical"] is True
    assert d["host_pool"]["cores_curve"]
    assert all(p["seq_order_ok"] for p in d["host_pool"]["cores_curve"])
    assert len(d["residues"]["entries"]) >= 4
    assert d["residues"]["walls"]
    assert _validate(out) == []


def test_chaos_soak_quick(tmp_path):
    out = str(tmp_path / "CHAOS_r99.json")
    d = _run_quick("chaos_soak.py", out)
    assert d["all_stable"] is True
    assert _validate(out) == []


def test_serve_soak_quick(tmp_path):
    """The admission service end to end at smoke scale: wall-clock SLO
    hold with online K adaptation, kill/restart convergence against an
    unkilled control, SIGTERM drain, and batch-runner decision parity."""
    out = str(tmp_path / "SERVE_r99.json")
    d = _run_quick("serve_soak.py", out)
    assert d["quick"] is True
    assert d["all_ok"] is True
    assert d["parity"]["decisions_identical"] is True
    assert d["kill_restart"]["lost_accepted_submissions"] == 0
    assert d["kill_restart"]["duplicated_admissions"] == 0
    assert d["kill_restart"]["decisions_identical"] is True
    assert d["kill_restart"]["digests_match"] is True
    assert d["drain"]["clean"] is True
    assert d["drain"]["wal_flushed"] is True
    assert d["wall"]["slo"]["held"] is True
    assert d["wall"]["slo"]["k_adapted"] is True
    assert _validate(out) == []


def test_dist_soak_quick(tmp_path):
    """The distributed control plane end to end at smoke scale: real
    child processes under the seeded supervisor, a wall-clock
    saturation round, all four process-kill arms recovering with zero
    lost/duplicated admissions bit-identical to the single-process
    control, and socket-fault classification through the proxy."""
    out = str(tmp_path / "DIST_r99.json")
    d = _run_quick("dist_soak.py", out)
    assert d["quick"] is True
    assert d["all_ok"] is True
    assert d["saturation"]["wall_clock"] is True
    assert d["saturation"]["ceiling_admissions_per_s"] > 0
    assert d["saturation"]["submitter_procs"] >= 2
    assert d["saturation"]["shard_procs"] >= 2
    for arm in ("submitter", "front_end_shard", "service_mid_cycle",
                "federation_worker"):
        k = d["kills"][arm]
        assert k["parity"] is True
        assert k["decisions_identical"] is True
        assert k["lost"] == 0
        assert k["duplicated"] == 0
    assert d["kills"]["service_mid_cycle"]["crash_exit"] == 17
    assert d["kills"]["federation_worker"]["epoch_resyncs"] >= 1
    assert d["socket_faults"]["ok"] is True
    assert d["dist"]["kill_log"]
    # the kueue_dist_* / kueue_rpc_* series sampled from the live run
    assert d["metrics"]["rpc"]["requests"] > 0
    assert d["metrics"]["dist"]["by_role"]["worker"]["kills"] == 1
    assert _validate(out) == []


def test_obs_soak_quick(tmp_path):
    """The telemetry plane end to end at smoke scale: interleaved
    traced/untraced arms on identically-built drivers, bit-identical
    decisions, a covering span roster, and working dump surfaces."""
    out = str(tmp_path / "OBS_r99.json")
    d = _run_quick("obs_soak.py", out)
    assert d["quick"] is True
    assert d["decisions_identical"] is True
    assert d["overhead"]["ratio"] <= 1.05
    assert d["spans_missing_host_phases"] == []
    assert d["dumps"]["flightrecorder_ok"] is True
    assert d["dumps"]["sigusr2_ok"] is True
    assert d["dumps"]["chrome_trace_events"] > 0
    assert d["control"]["interleaved"] is True
    assert _validate(out) == []
