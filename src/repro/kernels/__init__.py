"""Pallas TPU kernels of the two phase programs (TLMM, prefill, decode).

Whether a kernel runs compiled or in the Pallas interpreter is decided here,
from the backend, and nowhere else.  So is whether an op that picks its
path by itself (``tlmm_matmul``) takes the compiled kernel at all."""
from __future__ import annotations

import contextlib
import contextvars

import jax

_PARTITIONED = contextvars.ContextVar("repro_kernels_partitioned", default=False)


def interpret_mode() -> bool:
    """True on the CPU, where kernels run in the Pallas interpreter.

    Any other backend compiles them with Mosaic, so a TPU never runs a
    kernel in the interpreter."""
    return jax.default_backend() == "cpu"


@contextlib.contextmanager
def partitioned(on: bool = True):
    """Trace inside a program that GSPMD partitions over several devices.

    Mosaic cannot partition a kernel, so there :func:`compiled_kernels` is
    False and ops that choose their own path take their XLA reference."""
    token = _PARTITIONED.set(on)
    try:
        yield
    finally:
        _PARTITIONED.reset(token)


def compiled_kernels() -> bool:
    """Whether an op that decides from the platform runs its compiled
    kernel: on a backend Mosaic compiles for, outside a program partitioned
    over several devices."""
    return not interpret_mode() and not _PARTITIONED.get()
