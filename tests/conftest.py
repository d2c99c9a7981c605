import glob
import os

# The suites run on the CPU with eight virtual devices (the sharded
# paths need a mesh).  setdefault, not assignment: a caller who exports
# JAX_PLATFORMS or a device count decides.  Both must land before jax
# initializes a backend, hence before any kueue_tpu import.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


def tpu_present() -> bool:
    """Whether this host has a TPU, read from the device nodes (listed,
    not opened or followed): the test process is pinned to the CPU and
    must not touch the chip, which belongs to the one child that takes
    it."""
    return bool(glob.glob("/dev/accel[0-9]*")
                or glob.glob("/dev/vfio/[0-9]*"))


def pytest_report_header(config):
    return (f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']} "
            f"tpu_present={tpu_present()}")


def pytest_collection_modifyitems(config, items):
    """``tpu``-marked tests need the chip.  Without one they are
    deselected by platform — never passed by skipping."""
    if tpu_present():
        return
    gone = [it for it in items if it.get_closest_marker("tpu")]
    if gone:
        config.hook.pytest_deselected(items=gone)
        items[:] = [it for it in items if it not in gone]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: soak-tier tests excluded from the tier-1 run (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "tpu: needs a TPU on this host; deselected when there is none")


class FakeClock:
    """Shared virtual clock for the fake-cluster suites."""

    def __init__(self, now=1000.0):
        self.t = now

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


@pytest.fixture
def no_oracle_specs(monkeypatch):
    """For suites whose queues give a head one preempt-capable flavor at
    most: the walk has nothing to set against anything, so the batched
    reclaim oracle is never asked (``oracle_specs`` stays 0)."""
    from kueue_tpu.scheduler.preemption import Preemptor
    asked = []
    ask = Preemptor.reclaim_possible_batch
    monkeypatch.setattr(
        Preemptor, "reclaim_possible_batch",
        lambda self, queries, snapshot: asked.append(len(queries))
        or ask(self, queries, snapshot))
    yield
    assert not asked, f"oracle specs planned: {asked}"
