"""Jit wrapper for the SSD kernel (interpreted on CPU)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.ssd.ssd import ssd_pallas


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, B_, C, *, chunk: int = 256):
    return ssd_pallas(x, dt, A, B_, C, chunk=chunk,
                      interpret=interpret_mode())
