"""Which backend the Pallas kernels are about to run on."""

from __future__ import annotations

import jax

__all__ = ["pallas_interpret"]


def pallas_interpret() -> bool:
    """The ``interpret=`` flag for every ``pallas_call`` in the library:
    False on the TPU backend (Mosaic compiles the kernel), True on the
    CPU backend (tests and rehearsals), and an error anywhere else — a
    kernel silently interpreted on some other accelerator would be a
    fallback that hides the device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"apex_tpu's Pallas kernels run on the TPU backend (compiled) or "
        f"the CPU backend (interpret mode); the default backend here is "
        f"{backend!r}")
