"""The solver plane's one device decision.

Every jitted kernel in ``kueue_tpu.ops`` — the per-cycle admit scans,
the fused burst, the preemption search, the TAS and fair-sharing
kernels — runs on the device this module names, and nothing else in
the package picks one.  The choice belongs to the caller's environment:
the default JAX backend is the TPU on a chip host and the CPU when the
caller exports ``JAX_PLATFORMS=cpu`` (tests/conftest.py, the soaks).
"""

from __future__ import annotations


def solver_device():
    """The default JAX backend's first device.

    A platform the environment selected but JAX cannot initialize raises
    here (JAX's own RuntimeError) and nothing on the solve path catches
    it: a chip that vanished must stop the program, not reroute it."""
    import jax
    return jax.devices()[0]


def output_devices(out) -> set:
    """The device set a kernel's output lives on (its first array's).
    A default device and a mesh's shardings are requests; this is what
    happened, so the placement counters are read from it."""
    import jax
    return jax.tree_util.tree_leaves(out)[0].devices()


def on_accelerator(devices) -> bool:
    return all(d.platform != "cpu" for d in devices)
