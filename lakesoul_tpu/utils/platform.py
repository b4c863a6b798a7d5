"""What the process runs on, asked in one place: callers import this module
and call ``platform.on_tpu()``, so a test that wants the chip's branch patches
the one name here and every kernel module takes it."""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether JAX's default device is a TPU: where a Pallas kernel compiles
    (off one it runs in the interpreter, or gives way to its ``jnp`` twin)."""
    return jax.devices()[0].platform == "tpu"
