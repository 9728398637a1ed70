"""Pallas TPU kernels of the two phase programs (TLMM, prefill, decode).

Whether a kernel runs compiled or in the Pallas interpreter is decided here,
from the backend, and nowhere else."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU, where kernels run in the Pallas interpreter.

    Any other backend compiles them with Mosaic, so a TPU never runs a
    kernel in the interpreter."""
    return jax.default_backend() == "cpu"
