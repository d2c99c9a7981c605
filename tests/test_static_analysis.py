"""The invariant linter's own tests (kueue_tpu/analysis/).

Three layers, mirroring the acceptance contract:

- **fixtures** — each pass flags a seeded violation and accepts the
  minimal clean variant (the pass demonstrably *can* catch what it
  claims to catch);
- **real repo** — the full suite over the live codebase has zero
  unsuppressed findings and no stale baseline entries, and the
  baseline is strictly smaller than the first full-repo run's count
  (violations were fixed, not grandfathered);
- **fix guards** — decision-bit-identity tests for the concrete dtype
  fixes the pass surfaced in stream_pack.py (the int32 mi pipeline
  and the explicit-dtype ``_enc_str``).
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from kueue_tpu.analysis import (
    BASELINE_PATH,
    Context,
    ParsedFile,
    apply_baseline,
    load_baseline,
    run_all,
)
from kueue_tpu.analysis import (
    chaos_sites,
    dtypes,
    env_flags,
    purity,
    wal_order,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pf(path: str, src: str) -> ParsedFile:
    return ParsedFile.from_source(path, textwrap.dedent(src))


def codes(findings):
    return sorted({f.code for f in findings})


def ctx(tmp_path, **kw) -> Context:
    return Context(str(tmp_path), **kw)


# ---------------------------------------------------------------------------
# purity fixtures
# ---------------------------------------------------------------------------

def test_purity_flags_effects_reachable_from_jit(tmp_path):
    files = [pf("kueue_tpu/ops/fake.py", """
        import time
        import numpy as np
        import jax

        def _helper(x):
            return x + np.random.rand()

        def _kernel(x):
            t = time.time()
            y = _helper(x)
            z = float(y)
            return z + x.item()

        run = jax.jit(_kernel)
    """)]
    found = purity.run(files, ctx(tmp_path))
    assert "wall-clock" in codes(found)
    assert "np-random" in codes(found)        # via _helper reachability
    assert "traced-coercion" in codes(found)
    assert sum(f.code == "traced-coercion" for f in found) == 2


def test_purity_flags_global_mutation_and_host_io(tmp_path):
    files = [pf("kueue_tpu/parallel/fake.py", """
        import os
        from functools import partial
        import jax

        _CACHE = {}

        @partial(jax.jit, static_argnames=("k",))
        def _kernel(x, k):
            _CACHE[k] = x
            if os.environ.get("DEBUG"):
                print(x)
            return x
    """)]
    found = purity.run(files, ctx(tmp_path))
    assert "global-mutation" in codes(found)
    assert "host-io" in codes(found)


def test_purity_accepts_clean_kernel_and_host_code(tmp_path):
    # host-side orchestration in the same module may use clocks and
    # env vars freely: only jit-reachable code is kernel scope
    files = [pf("kueue_tpu/ops/fake.py", """
        import time
        import os
        import jax
        import jax.numpy as jnp

        def _kernel(x):
            return jnp.cumsum(x) * 2

        run = jax.jit(_kernel)

        def host_harness(x):
            t0 = time.time()
            if os.environ.get("KNOB"):
                print("host side is allowed to do this")
            return run(x), time.time() - t0
    """)]
    assert purity.run(files, ctx(tmp_path)) == []


def test_purity_ignores_files_without_jit_entries(tmp_path):
    files = [pf("kueue_tpu/ops/hostonly.py", """
        import time

        def pure_host(x):
            return time.time() + x
    """)]
    assert purity.run(files, ctx(tmp_path)) == []


# ---------------------------------------------------------------------------
# dtype fixtures
# ---------------------------------------------------------------------------

def test_dtype_flags_dtypeless_and_platform_creations(tmp_path):
    files = [pf("kueue_tpu/ops/packing.py", """
        import numpy as np

        def build(n):
            a = np.zeros(n)
            b = np.arange(n)
            c = a.astype(int)
            return a, b, c
    """)]
    found = dtypes.run(files, ctx(tmp_path))
    assert codes(found) == ["dtype-less", "platform-dtype"]
    assert sum(f.code == "dtype-less" for f in found) == 2


def test_dtype_flags_schema_mismatch_in_ensure_and_row_planes(tmp_path):
    files = [pf("kueue_tpu/ops/stream_pack.py", """
        import numpy as np

        _ROW_PLANES = {
            "wl_req": (0, np.int64, "R"),
            "mystery": (0, np.int32, None),
        }

        def views(arena, C, M):
            arena.ensure("wl_prio", (C, M), np.int16, 0)
            arena.ensure("u_cq0", (C, 4), np.int32, 0)
    """)]
    found = dtypes.run(files, ctx(tmp_path))
    assert sum(f.code == "schema-mismatch" for f in found) == 2
    assert sum(f.code == "unknown-plane" for f in found) == 1


def test_dtype_accepts_clean_creations(tmp_path):
    files = [pf("kueue_tpu/cache/arena.py", """
        import numpy as np

        def build(arena, n):
            a = np.zeros(n, dtype=np.int32)
            b = np.arange(n, dtype=np.int32)
            c = np.full((n,), -1, np.int16)
            arena.ensure("wl_req", (n, 4), np.int32, 0)
            return a, b, c
    """)]
    assert dtypes.run(files, ctx(tmp_path)) == []


def test_dtype_flags_nonint32_tighten_plane(tmp_path):
    files = [pf("kueue_tpu/ops/packing.py", """
        TIGHTEN_PLANES = ("wl_req", "vec_ok", "no_such_plane")
    """)]
    found = dtypes.run(files, ctx(tmp_path))
    assert "schema-mismatch" in codes(found)   # vec_ok is bool
    assert "unknown-plane" in codes(found)


# ---------------------------------------------------------------------------
# wal-order fixtures
# ---------------------------------------------------------------------------

_WAL_CLEAN = """
    class Driver:
        def _apply_admission(self, wl):
            self._wal.log(_journal.admit_op(wl))
            _chaos.ACTIVE.crashpoint("wal.admit")
            self.workloads[wl.key] = wl

        def create_workload(self, wl):
            # store repopulation path: no journaling, out of scope
            self.workloads[wl.key] = wl
"""


def test_wal_accepts_append_chaos_mutation_order(tmp_path):
    files = [pf("kueue_tpu/controller/driver.py", _WAL_CLEAN)]
    assert wal_order.run(files, ctx(tmp_path)) == []


def test_wal_flags_mutation_before_append(tmp_path):
    files = [pf("kueue_tpu/controller/driver.py", """
        class Driver:
            def _apply_admission(self, wl):
                self.workloads[wl.key] = wl
                self._wal.log(_journal.admit_op(wl))
    """)]
    found = wal_order.run(files, ctx(tmp_path))
    assert codes(found) == ["mutation-before-append"]


def test_wal_flags_chaos_point_outside_window(tmp_path):
    files = [pf("kueue_tpu/controller/driver.py", """
        class Driver:
            def _evict(self, wl):
                self._wal.log(_journal.evict_op(wl.key))
                set_evicted_condition(wl, "r", "m", 0.0)
                _chaos.ACTIVE.crashpoint("wal.evict")
    """)]
    found = wal_order.run(files, ctx(tmp_path))
    assert codes(found) == ["chaos-outside-window"]


def test_wal_flags_unjournaled_mutation_in_wal_scope(tmp_path):
    files = [pf("kueue_tpu/controller/driver.py", """
        class Driver:
            def finish(self, wl):
                self._wal.log(_journal.admit_op(wl))
                set_finished_condition(wl, "t", "m", 0.0)
    """)]
    found = wal_order.run(files, ctx(tmp_path))
    assert "unjournaled-mutation" in codes(found)
    assert "missing-journal-kind" in codes(found)


def test_wal_flags_wholesale_journal_removal(tmp_path):
    # both the append and the chaos point deleted: the per-function
    # scope can't see it, the module-wide kind check still does
    files = [pf("kueue_tpu/controller/driver.py", """
        class Driver:
            def _evict(self, wl):
                set_evicted_condition(wl, "r", "m", 0.0)
    """)]
    found = wal_order.run(files, ctx(tmp_path))
    assert codes(found) == ["missing-journal-kind"]


# ---------------------------------------------------------------------------
# chaos-sites fixtures
# ---------------------------------------------------------------------------

_INJECTOR_DOC = '''
    """Injector.

    ==============================  =====================
    site                            effect
    ==============================  =====================
    ``cycle.start``                 crash before a cycle
    ``wal.admit``                   crash mid-admit
    ==============================  =====================
    """
'''


def test_chaos_sites_clean_when_all_three_sets_agree(tmp_path):
    files = [
        pf("kueue_tpu/chaos/injector.py", _INJECTOR_DOC),
        pf("kueue_tpu/driver.py", """
            def f(inj):
                inj.crashpoint("cycle.start")
                inj.hit("wal.admit")
        """),
    ]
    c = ctx(tmp_path, extra_sources={"tests/test_x.py": textwrap.dedent("""
        def test_y(inj):
            inj.arm("cycle.start", at=1)
            inj.arm("wal.admit", at=2)
    """)})
    assert chaos_sites.run(files, c) == []


def test_chaos_sites_flags_every_kind_of_drift(tmp_path):
    files = [
        pf("kueue_tpu/chaos/injector.py", _INJECTOR_DOC),
        pf("kueue_tpu/driver.py", """
            def f(inj):
                inj.crashpoint("cycle.start")
                inj.crashpoint("secret.site")
        """),
    ]
    c = ctx(tmp_path, extra_sources={"tests/test_x.py": textwrap.dedent("""
        def test_y(inj):
            inj.arm("cycle.start", at=1)
            inj.arm("tpyo.site", at=1)
    """)})
    found = chaos_sites.run(files, c)
    by = {f.code: f.symbol for f in found}
    assert by["undocumented-site"] == "secret.site"
    assert by["unthreaded-site"] == "wal.admit"
    assert by["unknown-armed-site"] == "tpyo.site"
    untested = {f.symbol for f in found if f.code == "untested-site"}
    assert untested == {"secret.site", "wal.admit"}


# ---------------------------------------------------------------------------
# env-flags fixtures
# ---------------------------------------------------------------------------

_FLAGS = {"KUEUE_TPU_FOO", "KUEUE_TPU_BAR"}
_README_OK = """
    ## Environment flags

    | flag | type | default | effect |
    |------|------|---------|--------|
    | `KUEUE_TPU_FOO` | bool | `1` | Foo. |
    | `KUEUE_TPU_BAR` | int | `0` | Bar. |
"""


def test_env_flags_clean_registry_reads(tmp_path):
    files = [pf("kueue_tpu/mod.py", """
        from .features import env_value

        def f():
            return env_value("KUEUE_TPU_FOO")
    """)]
    c = ctx(tmp_path, env_flags=_FLAGS,
            extra_sources={"README.md": textwrap.dedent(_README_OK)})
    assert env_flags.run(files, c) == []


def test_env_flags_flags_adhoc_reads_and_unregistered_names(tmp_path):
    files = [pf("kueue_tpu/mod.py", """
        import os
        import os as _os

        def f():
            a = os.environ.get("KUEUE_TPU_FOO", "1")
            b = _os.environ.get("KUEUE_TPU_BAR", "0")
            c = os.environ["KUEUE_TPU_FOO"]
            d = os.getenv("KUEUE_TPU_FOO")
            e = "KUEUE_TPU_TYPO"
            # writes are allowed: harnesses configure children
            os.environ["KUEUE_TPU_FOO"] = "1"
            os.environ.setdefault("KUEUE_TPU_BAR", "0")
            return a, b, c, d, e
    """)]
    c = ctx(tmp_path, env_flags=_FLAGS,
            extra_sources={"README.md": textwrap.dedent(_README_OK)})
    found = env_flags.run(files, c)
    assert sum(f.code == "ad-hoc-env-read" for f in found) == 4
    assert sum(f.code == "unregistered-flag" for f in found) == 1


def test_env_flags_flags_reads_outside_the_registered_prefix(tmp_path):
    files = [pf("kueue_tpu/mod.py", """
        import os

        def f():
            return os.environ.get("KUEUE_BURST_X", "1") != "0"
    """)]
    c = ctx(tmp_path, env_flags=_FLAGS,
            extra_sources={"README.md": textwrap.dedent(_README_OK)})
    found = env_flags.run(files, c)
    assert [(f.code, f.symbol) for f in found] == [
        ("ad-hoc-env-read", "KUEUE_BURST_X")]


@pytest.mark.parametrize("rel", [
    "kueue_tpu/controller/driver.py", "kueue_tpu/ops/burst.py",
    "kueue_tpu/ops/stream_pack.py", "kueue_tpu/ops/solver.py"])
def test_main_path_reads_no_environment(rel):
    """From Driver.schedule_burst down to the kernels' launch sites
    nothing asks the environment what to do, under any name: what
    varies is an argument or a registered flag read through
    features.env_value."""
    with open(os.path.join(ROOT, rel)) as f:
        parsed = ParsedFile.from_source(rel, f.read())
    os_names = env_flags._os_aliases(parsed.tree)
    reads = [read for node in ast.walk(parsed.tree)
             if (read := env_flags._env_read(node, os_names)) is not None]
    assert reads == [], (rel, reads)


def test_env_flags_checks_readme_table_both_ways(tmp_path):
    c = ctx(tmp_path, env_flags=_FLAGS, extra_sources={
        "README.md": textwrap.dedent("""
            ## Environment flags

            | `KUEUE_TPU_FOO` | bool | `1` | Foo. |
            | `KUEUE_TPU_GHOST` | int | `0` | Gone. |
        """)})
    found = env_flags.run([], c)
    by = {f.code: f.symbol for f in found}
    assert by["readme-missing-flag"] == "KUEUE_TPU_BAR"
    assert by["readme-unknown-flag"] == "KUEUE_TPU_GHOST"


def test_env_flags_flags_missing_readme_section(tmp_path):
    c = ctx(tmp_path, env_flags=_FLAGS,
            extra_sources={"README.md": "# nothing here\n"})
    assert codes(env_flags.run([], c)) == ["readme-missing-table"]


# ---------------------------------------------------------------------------
# the real repo is lint-clean, and the baseline only shrinks
# ---------------------------------------------------------------------------

def test_repo_has_zero_unsuppressed_findings():
    findings = run_all(ROOT)
    baseline = load_baseline(BASELINE_PATH)
    unsuppressed, suppressed, stale = apply_baseline(findings, baseline)
    assert unsuppressed == [], "\n".join(f.render() for f in unsuppressed)
    assert stale == [], f"stale baseline entries (delete them): {stale}"


def test_baseline_is_strictly_smaller_than_first_full_run():
    baseline = load_baseline(BASELINE_PATH)
    first = baseline["first_full_run_findings"]
    assert first > 0
    assert len(baseline["entries"]) < first, \
        "grandfathering must shrink the finding count, not preserve it"


def test_cli_json_output_and_budget():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "lint_invariants.py"), "--json"],
        capture_output=True, text=True, cwd=ROOT, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["ok"] is True
    assert [p["name"] for p in report["passes"]] == [
        "purity", "dtype", "wal-order", "chaos-sites", "env-flags",
        "metrics-doc"]
    assert report["findings"] == []
    assert report["elapsed_s"] < 10.0, "the lint must stay tier-1 fast"


# ---------------------------------------------------------------------------
# decision-bit-identity guards for the dtype fixes in stream_pack.py
# ---------------------------------------------------------------------------

def test_enc_str_explicit_dtype_is_bit_identical():
    from kueue_tpu.ops.stream_pack import _enc_str
    for arr in (np.array(["abc", "de", ""]),
                np.array(["x"], dtype="U7"),
                np.array([], dtype="U1")):
        out = _enc_str(arr, 8)
        ref = np.char.encode(np.asarray(arr).astype("U8"),
                             "ascii").astype("S8")
        assert out.dtype == ref.dtype and np.array_equal(out, ref)


def test_mi_pipeline_int32_matches_int64_reference():
    # the per-CQ slot-index pipeline in _init_full was widened to int64
    # by np.arange's default; the int32 fix must be value-identical
    rng = np.random.default_rng(7)
    for n in (1, 5, 257):
        ci_sorted = np.sort(rng.integers(0, 9, n))
        first = np.ones(n, dtype=bool)
        first[1:] = ci_sorted[1:] != ci_sorted[:-1]
        # old (default-dtype) computation
        seg64 = np.maximum.accumulate(np.where(first, np.arange(n), 0))
        mi64 = (np.arange(n) - seg64).astype(np.int64)
        # the fixed computation, as written in _init_full
        idx = np.arange(n, dtype=np.int32)
        seg32 = np.maximum.accumulate(
            np.where(first, idx, np.int32(0)))
        mi32 = idx - seg32
        assert mi32.dtype == np.int32
        assert np.array_equal(mi32, mi64)


def test_stream_pack_mi_planes_are_int32_end_to_end():
    # regression guard: a full build must hand int32 slot indices to
    # the order maintainers and grids; it places its rows as a delta
    # window does, by the per-CQ rank of _cq_mi
    import inspect
    from kueue_tpu.ops import stream_pack
    src = inspect.getsource(stream_pack._cq_mi)
    assert "mi = np.empty(rec.n_rows, dtype=np.int32)" in src
    assert "np.arange(rec.n_rows, dtype=np.int32)" in src
    assert "_place_rows(state, records)" in inspect.getsource(
        stream_pack._init_full)
    assert "mi_a32" not in inspect.getsource(stream_pack)
