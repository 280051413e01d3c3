"""Mamba-2 SSD chunked scan as a Pallas TPU kernel  [arXiv:2405.21060].

Grid = (batch, heads, n_chunks); TPU executes the chunk dim sequentially,
so the inter-chunk recurrent state [head_dim, d_state] lives in VMEM
scratch, while the intra-chunk work is dense MXU matmuls over
[chunk, chunk] and [chunk, d_state] tiles.  The kernel fuses what the
CUDA reference splits into four launches: decay cumsum, masked
(CB^T)-attention, state update, and inter-chunk output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, chunk: int, n_chunks: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)           # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)         # [Q, 1]
    A = a_ref[pl.program_id(1)]                   # scalar (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)          # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)          # [Q, N]

    # Everything stays 2-D and the prefix sums are masked reductions:
    # Mosaic lowers neither cumsum nor 1-D vectors.  A [Q, 1] column
    # becomes a [1, Q] row through the diagonal of a masked [Q, Q] tile.
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj
    dA = dt * A                                   # [Q, 1]
    dA_row = jnp.sum(jnp.where(ii == jj, dA, 0.0), axis=0, keepdims=True)
    dt_row = jnp.sum(jnp.where(ii == jj, dt, 0.0), axis=0, keepdims=True)
    seg = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1,
                  keepdims=True)                  # [Q, 1] inclusive cumsum
    seg_row = jnp.sum(jnp.where(ii <= jj, dA, 0.0), axis=0,
                      keepdims=True)              # [1, Q] the same, as a row
    seg_last = jnp.sum(dA, axis=0, keepdims=True)  # [1, 1]

    # intra-chunk: att[i,j] = (C_i . B_j) exp(seg_i - seg_j) dt_j, j <= i
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    decay = jnp.exp(jnp.where(causal, seg - seg_row, -jnp.inf))
    att = cb * decay * dt_row
    y = jax.lax.dot(att, x, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += (C_i * exp(seg_i)) . state^T
    state = state_scr[...]                        # [P, N]
    c_tilde = Cm * jnp.exp(seg)
    y += jax.lax.dot_general(c_tilde, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: state = exp(seg_last) * state + (w_in * x)^T B
    w_in = jnp.exp(seg_last - seg) * dt           # [Q, 1]
    s_c = jax.lax.dot_general(x * w_in, Bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [P,N]
    new_state = jnp.exp(seg_last) * state + s_c
    state_scr[...] = new_state

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = new_state.astype(st_ref.dtype)


def ssd_pallas(x, dt, A, B_, C, *, chunk: int, interpret: bool = False):
    """x: [B, L, H, P]; dt: [B, L, H] (post-softplus, f32); A: [H];
    B_/C: [B, L, G, N].  Returns (y [B,L,H,P] f32, state [B,H,P,N] f32)."""
    Bs, L, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    xt = x.transpose(0, 2, 1, 3)                      # [B,H,L,P]
    dtt = dt.transpose(0, 2, 1)[..., None]            # [B,H,L,1]
    at = A.astype(jnp.float32)                        # [H], whole in SMEM
    bt = B_.transpose(0, 2, 1, 3)                     # [B,G,L,N]
    ct = C.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, st = pl.pallas_call(
        kernel,
        grid=(Bs, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bs, H, L, P), jnp.float32),
            jax.ShapeDtypeStruct((Bs, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, at, bt, ct)
    return y.transpose(0, 2, 1, 3), st
