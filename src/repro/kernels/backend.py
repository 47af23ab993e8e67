"""Which way a Pallas kernel runs on the current backend.

The kernels are written for the TPU. On the CPU, where the tests run, they
run in Pallas interpret mode. Any other backend is an error: a kernel that
silently falls back to the interpreter on an accelerator hides the device.
"""
from __future__ import annotations

import jax


def use_interpret(platform: str | None = None) -> bool:
    """True on ``cpu`` (interpret), False on ``tpu`` (compile); raises on
    any other platform. ``platform`` defaults to ``jax.default_backend()``."""
    platform = platform or jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels compile on tpu and interpret on cpu; "
        f"backend {platform!r} is neither")
