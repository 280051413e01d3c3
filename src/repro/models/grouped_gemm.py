"""Grouped (per-expert) GEMM with a memory-safe custom VJP.

``lax.ragged_dot``'s default autodiff materializes dense per-group
expansions — f32[E, M, K] / [M, E*N] temporaries that reach hundreds of
GB per device for production MoE trains (observed 641 GB/device for
deepseek-v2-lite train_4k).  Both gradients are themselves grouped GEMMs,
so we register them explicitly:

    y              = ragged_dot(x, w, gs)            [M,N]
    dx             = ragged_dot'(dy, w, gs)           contract N -> [M,K]
    dw[g]          = x_g^T dy_g  (ragged-contracting) -> [G,K,N]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import RaggedDotDimensionNumbers, ragged_dot_general

_DLHS_DIMS = RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (2,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
_DRHS_DIMS = RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


@jax.custom_vjp
def grouped_gemm(lhs, rhs, group_sizes):
    """lhs: [M, K] rows sorted by group; rhs: [G, K, N]; group_sizes: [G].
    Returns [M, N] where row m is lhs[m] @ rhs[group(m)]."""
    return lax.ragged_dot(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return grouped_gemm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, dy):
    lhs, rhs, group_sizes = res
    d_lhs = ragged_dot_general(dy, rhs, group_sizes, _DLHS_DIMS)
    d_rhs = ragged_dot_general(lhs.astype(jnp.float32),
                               dy.astype(jnp.float32), group_sizes,
                               _DRHS_DIMS).astype(rhs.dtype)
    return d_lhs.astype(lhs.dtype), d_rhs, None


grouped_gemm.defvjp(_fwd, _bwd)
