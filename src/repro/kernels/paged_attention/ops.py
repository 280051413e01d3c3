"""Jit wrapper for paged decode attention (interpreted on CPU)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.paged_attention import \
    paged_attention_pallas


@functools.partial(jax.jit, static_argnames=("pages_per_tile",))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    pages_per_tile: int = 4):
    """``pages_per_tile`` KV pages stream per grid step (static): 4 is
    the default tiling; 1 recovers the single-page-per-step baseline
    (the before/after axis of ``bench.profile.paged_kernel_microbench``)."""
    return paged_attention_pallas(
        q, k_pages, v_pages, block_tables, context_lens,
        pages_per_tile=pages_per_tile,
        interpret=interpret_mode())
