"""Jit wrapper for the flash-attention kernel: compiled on TPU,
interpreted on CPU (which validates the kernel body in Python)."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    bq: int = 128, bk: int = 128):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  softcap=softcap, bq=bq, bk=bk,
                                  interpret=interpret_mode())
