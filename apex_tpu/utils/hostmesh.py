"""Bootstrap a virtual multi-device CPU "mesh" in the current process.

The reference requires real GPUs for every distributed test (SURVEY.md §4).
We instead validate DP/TP/PP/SP shardings on XLA's CPU backend with
``--xla_force_host_platform_device_count=N``.  Two subtleties, learned the
hard way (VERDICT r1 item 1):

- ``JAX_PLATFORMS`` in the environment may name an accelerator (on a TPU
  machine JAX picks the TPU by default), so the platform is switched with
  ``jax.config.update("jax_platforms", "cpu")`` — which wins over the
  environment, and only works before the backend is first touched.
- ``XLA_FLAGS`` may already carry a (different) device-count flag; it must
  be replaced, not merely left alone.

This switch is process-wide and effectively irreversible once the CPU
backend initializes: callers that also need a real accelerator in the same
process must do that work *first*, or run this in a subprocess (the driver
runs ``dryrun_multichip`` in its own process).
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def force_virtual_cpu_devices(n_devices: int, verify: bool = True) -> None:
    """Force the CPU platform with ``n_devices`` virtual devices.

    Must run before the JAX backend is first used (importing jax is fine;
    calling ``jax.devices()`` etc. is not).  Raises if a backend with fewer
    devices was already initialized.

    ``verify=False`` skips the device-count check — which itself
    INITIALIZES the backend. The multi-process bootstrap
    (:func:`apex_tpu.parallel.multiproc.initialize`) needs that:
    ``jax.distributed.initialize`` refuses to run after any backend use,
    so it sets the flags unverified, rendezvouses, and only then counts
    devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags:
        flags = re.sub(rf"{_FLAG}=\d+", f"{_FLAG}={n_devices}", flags)
    else:
        flags = (flags + f" {_FLAG}={n_devices}").strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    if verify and jax.device_count() < n_devices:
        raise RuntimeError(
            f"needed {n_devices} virtual CPU devices but the "
            f"{jax.default_backend()} backend is already initialized with "
            f"{jax.device_count()} device(s); call force_virtual_cpu_devices "
            "before any JAX backend use (or in a fresh process)")
