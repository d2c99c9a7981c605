"""Accelerator-route smoke test.

The test process itself is pinned to the virtual CPU mesh by conftest
and never touches the chip: a chip belongs to one process, so the
accelerator run happens in ONE child with a clean JAX environment.  The
child solves a packed cycle on the default device and checks the
decisions against the scalar host oracle.

The test carries the ``tpu`` marker: on a host without a TPU it is
deselected by platform (tests/conftest.py), not passed by skipping, and
on a host with one every failure — no device, a timeout, a diverging
decision — fails.  ``chip_smoke.py`` is the full-width twin.
"""

import json
import os
import subprocess
import sys

import pytest

_SUBPROCESS = r'''
import json
import sys

import numpy as np
import jax

dev = jax.devices()[0]
assert dev.platform != "cpu", f"default JAX backend is {dev.platform}"

import __graft_entry__ as ge
from kueue_tpu.ops.cycle import classify_np, solve_cycle
from kueue_tpu.parallel import cycle_args

_, _, _, packed = ge._packed_cycle(n_cohorts=4, cqs_per_cohort=4,
                                   n_workloads=64, contended=True)
ref = classify_np(packed)                      # scalar host oracle
out = solve_cycle(*cycle_args(packed), depth=packed.depth, run_scan=False)
fit_slot0, borrows0 = [np.asarray(jax.device_get(o))
                       for o in (out[4], out[5])]
ok = (np.array_equal(fit_slot0, ref["fit_slot0"])
      and np.array_equal(borrows0, ref["borrows0"]))
print(json.dumps({
    "platform": dev.platform,
    # jax.default_device is a hint, so check the output's device set
    "on_accel": all(d.platform != "cpu" for d in out[4].devices()),
    "decisions_match": bool(ok),
    "heads": int(packed.wl_count),
}))
sys.exit(0 if ok else 1)
'''


@pytest.mark.tpu
def test_accel_solve_matches_host_oracle():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["decisions_match"], result
    assert result["on_accel"], result
