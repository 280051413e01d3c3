"""Mamba-2 SSD (state-space duality) mixer  [arXiv:2405.21060].

Chunked semiseparable algorithm: within a chunk the output is computed as
masked attention-like dense work (MXU friendly); across chunks a small
recurrence over per-chunk states carries long-range information.  The
single-token decode path is the O(1) recurrent update used by the serving
engine.  This module is also the pure-jnp oracle for ``kernels/ssd``.

The input projection is stored as *separate* matrices (w_z / w_x / w_B /
w_C / w_dt) rather than the fused in_proj of the CUDA reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, SSMConfig
from repro.models.layers import _normal, rms_norm


def init_mamba(key, cfg: ModelConfig, dtype):
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    ks = jax.random.split(key, 10)
    std = d ** -0.5
    u = jax.random.uniform(ks[0], (nh,), jnp.float32)
    dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    return {
        "ln": jnp.zeros((d,), dtype),
        "w_z": _normal(ks[1], (d, di), std, dtype),
        "w_x": _normal(ks[2], (d, di), std, dtype),
        "w_B": _normal(ks[3], (d, gn), std, dtype),
        "w_C": _normal(ks[4], (d, gn), std, dtype),
        "w_dt": _normal(ks[5], (d, nh), std, dtype),
        "conv_x": _normal(ks[6], (s.d_conv, di), s.d_conv ** -0.5, dtype),
        "conv_B": _normal(ks[7], (s.d_conv, gn), s.d_conv ** -0.5, dtype),
        "conv_C": _normal(ks[8], (s.d_conv, gn), s.d_conv ** -0.5, dtype),
        "conv_bx": jnp.zeros((di,), dtype),
        "conv_bB": jnp.zeros((gn,), dtype),
        "conv_bC": jnp.zeros((gn,), dtype),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": dt_bias,
        "gn": jnp.zeros((di,), dtype),
        "out_proj": _normal(ks[9], (di, d), di ** -0.5, dtype),
    }


def _causal_conv(u, w, b):
    """Depthwise causal conv1d.  u: [B,L,C]; w: [K,C]."""
    K = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + u.shape[1], :] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def ssd_chunked(x, dt, A, B_, C, chunk: int):
    """Chunked SSD scan.

    x:  [B, L, H, P]   (values)
    dt: [B, L, H]      (post-softplus step sizes, float32)
    A:  [H]            (negative decay rates)
    B_: [B, L, G, N]   (input maps)
    C:  [B, L, G, N]   (output maps)
    Returns y [B, L, H, P] (float32 pre-cast) and final state [B,H,P,N] f32.
    """
    Bsz, L, H, Pd = x.shape
    G, N = B_.shape[2], B_.shape[3]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    rep = H // G

    xc = x.reshape(Bsz, nc, chunk, H, Pd)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(jnp.float32)
    Bh = jnp.repeat(B_.reshape(Bsz, nc, chunk, G, N), rep, axis=3)
    Ch = jnp.repeat(C.reshape(Bsz, nc, chunk, G, N), rep, axis=3)

    dA = dtc * A                               # [B,nc,Q,H]  (negative)
    seg = jnp.cumsum(dA, axis=2)               # within-chunk cumulative decay

    # ---- intra-chunk (dense, causal-masked) ----
    cb = jnp.einsum("bcihn,bcjhn->bchij", Ch.astype(jnp.float32),
                    Bh.astype(jnp.float32))
    seg_h = seg.transpose(0, 1, 3, 2)          # [B,nc,H,Q]
    diff = seg_h[..., :, None] - seg_h[..., None, :]   # seg_i - seg_j
    ii = jnp.arange(chunk)
    causal = (ii[:, None] >= ii[None, :])[None, None, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))  # mask pre-exp: no ovf
    att = cb * decay * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", att.astype(x.dtype), xc)

    # ---- per-chunk states:  S_c = sum_j exp(seg_last - seg_j) dt_j x_j B_j
    last = seg[:, :, -1:, :]
    w_in = jnp.exp(last - seg) * dtc           # [B,nc,Q,H]
    S = jnp.einsum("bcjh,bcjhp,bcjhn->bchpn",
                   w_in, xc.astype(jnp.float32), Bh.astype(jnp.float32))

    # ---- inter-chunk recurrence over per-chunk states ----
    chunk_decay = jnp.exp(last[:, :, 0, :])    # [B,nc,H]

    def step(h_prev, inp):
        S_c, dec_c = inp
        h_next = dec_c[:, :, None, None] * h_prev + S_c
        return h_next, h_prev                  # emit state *before* chunk

    S_t = S.transpose(1, 0, 2, 3, 4)
    dec_t = chunk_decay.transpose(1, 0, 2)
    h0 = jnp.zeros((Bsz, H, Pd, N), jnp.float32)
    h_final, h_before = lax.scan(step, h0, (S_t, dec_t))
    h_before = h_before.transpose(1, 0, 2, 3, 4)  # [B,nc,H,P,N]

    # ---- inter-chunk output:  y_i += (C_i * exp(seg_i)) . h_before ----
    y_inter = jnp.einsum(
        "bcihn,bchpn->bcihp",
        Ch.astype(jnp.float32) * jnp.exp(seg)[..., None], h_before)

    y = y_intra.astype(jnp.float32) + y_inter
    return y.reshape(Bsz, L, H, Pd), h_final


def mamba_forward(p, cfg: ModelConfig, x, return_state: bool = False,
                  use_kernel: bool = False):
    """Full-sequence Mamba-2 block.  x: [B, L, d]."""
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    B, L, _ = x.shape

    h = rms_norm(x, p["ln"], cfg.norm_eps)
    z = h @ p["w_z"]
    x_raw = h @ p["w_x"]
    B_raw = h @ p["w_B"]
    C_raw = h @ p["w_C"]
    dt_raw = h @ p["w_dt"]

    xs = _causal_conv(x_raw, p["conv_x"], p["conv_bx"])
    Bv = _causal_conv(B_raw, p["conv_B"], p["conv_bB"])
    Cv = _causal_conv(C_raw, p["conv_C"], p["conv_bC"])

    xs = xs.reshape(B, L, nh, s.head_dim)
    Bv = Bv.reshape(B, L, s.n_groups, s.d_state)
    Cv = Cv.reshape(B, L, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    pad = (-L) % s.chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bv = jnp.pad(Bv, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cv = jnp.pad(Cv, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if use_kernel:
        from repro.kernels.ssd import ops as ssd_ops
        y, state = ssd_ops.ssd(xs, dt, A, Bv, Cv, chunk=s.chunk)
    else:
        y, state = ssd_chunked(xs, dt, A, Bv, Cv, s.chunk)
    if pad:
        y = y[:, :L]
    y = y + xs[:, :L].astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, L, di)
    y = rms_norm(y.astype(x.dtype) * jax.nn.silu(z.astype(jnp.float32)
                                                 ).astype(x.dtype),
                 p["gn"], cfg.norm_eps)
    out = y @ p["out_proj"]
    if return_state:
        tail = s.d_conv - 1
        def tail_of(u):
            if L >= tail:
                return u[:, L - tail:L]
            return jnp.pad(u, ((0, 0), (tail - L, 0), (0, 0)))
        conv_state = {"x": tail_of(x_raw), "B": tail_of(B_raw),
                      "C": tail_of(C_raw)}
        return out, (conv_state, state)
    return out


def mamba_decode(p, cfg: ModelConfig, x, conv_state, ssm_state):
    """Single-token recurrent update.

    x: [B,1,d]; conv_state: {"x": [B,K-1,di], "B": [B,K-1,gn], "C": ...}
    (pre-conv history); ssm_state: [B, H, P, N] float32.
    """
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)

    h = rms_norm(x[:, 0], p["ln"], cfg.norm_eps)
    z = h @ p["w_z"]
    x_new = h @ p["w_x"]
    B_new = h @ p["w_B"]
    C_new = h @ p["w_C"]
    dt_raw = h @ p["w_dt"]

    def conv_step(hist, new, w, b):
        cat = jnp.concatenate([hist, new[:, None, :]], axis=1)
        out = jnp.einsum("bkc,kc->bc", cat[:, -w.shape[0]:], w) + b
        return jax.nn.silu(out), cat[:, 1:]

    xs, nhx = conv_step(conv_state["x"], x_new, p["conv_x"], p["conv_bx"])
    Bv, nhB = conv_step(conv_state["B"], B_new, p["conv_B"], p["conv_bB"])
    Cv, nhC = conv_step(conv_state["C"], C_new, p["conv_C"], p["conv_bC"])
    new_conv = {"x": nhx, "B": nhB, "C": nhC}

    xs = xs.reshape(-1, nh, s.head_dim)
    rep = nh // s.n_groups
    Bv = jnp.repeat(Bv.reshape(-1, s.n_groups, s.d_state), rep, axis=1)
    Cv = jnp.repeat(Cv.reshape(-1, s.n_groups, s.d_state), rep, axis=1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)

    new_state = (dA[:, :, None, None] * ssm_state
                 + jnp.einsum("bh,bhp,bhn->bhpn", dt,
                              xs.astype(jnp.float32), Bv.astype(jnp.float32)))
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Cv.astype(jnp.float32))
    y = y + xs.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(-1, di)
    y = rms_norm(y.astype(x.dtype) * jax.nn.silu(z), p["gn"], cfg.norm_eps)
    out = (y @ p["out_proj"])[:, None, :]
    return out, (new_conv, new_state)
