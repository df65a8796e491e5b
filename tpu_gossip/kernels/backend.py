"""Where the repo's Pallas kernels run.

A kernel compiles through Mosaic for the chip, or runs in interpret mode
when the computation targets the CPU: the process's default backend, or a
``jax.default_device`` scope naming a CPU device (the chip smoke's CPU
oracle runs in the same process as its TPU run). jit keys its trace on
the default device, so each target gets its own trace.
"""

from __future__ import annotations

import jax

__all__ = ["interpret_default"]


def interpret_default() -> bool:
    """True iff a kernel launched now would run on a CPU device."""
    dev = jax.config.jax_default_device
    if dev is not None:
        return (dev if isinstance(dev, str) else dev.platform) == "cpu"
    return jax.default_backend() == "cpu"
