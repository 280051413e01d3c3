"""Plain reference of a Mamba-2 decoder: the forward pass over a whole
sequence with the SSD layer as its sequential recurrence, one time step
after another, in straightforward ``jax.numpy``.  It imports nothing of
the program.

Per layer: pre-norm; input projections z, x, B, C and dt; a causal
depthwise convolution of width ``d_conv`` with SiLU on x, B and C;
dt = softplus(dt + dt_bias), A = -exp(A_log); per head the state
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T and the output
y_t = h_t C_t + D x_t; then y * SiLU(z), an RMS norm over the inner
width, the output projection, and the residual add.  A final norm and
logits against the tied embedding.  One group of B and C (n_groups 1).
RMS norms scale by ``1 + w``.  RMS norm epsilon 1e-6 against the
published 1e-5 is a departure shared with the program.

``cdt`` as in ``llama_dense``: float32 at ``HIGHEST`` is the reference;
bfloat16 weights and activations the control.  The recurrent state is
float32 in both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

BATCH = 8


def draw(name: str, key, shape, spec):
    """The value law of each weight, by the name of its leaf."""
    d, K = spec["d_model"], spec["d_conv"]
    di = spec["expand"] * d

    def normal(std):
        return jax.random.normal(key, shape, jnp.float32) * std

    def uniform(lo, hi):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)

    if name == "embed":
        rows = lax.broadcasted_iota(jnp.int32, shape, 0)
        return jnp.where(rows < spec["vocab_size"], normal(d ** -0.5), 0.0)
    if name in ("ln", "final_norm", "gn", "conv_bx", "conv_bB", "conv_bC"):
        return normal(0.1)
    if name in ("w_z", "w_x", "w_B", "w_C", "w_dt"):
        return normal(d ** -0.5)
    if name in ("conv_x", "conv_B", "conv_C"):
        return normal(K ** -0.5)
    if name == "out_proj":
        return normal(di ** -0.5)
    if name == "A_log":
        return jnp.log(uniform(1.0, 16.0))
    if name == "D":
        return uniform(0.5, 1.5)
    if name == "dt_bias":
        # softplus^-1 of a step size log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(uniform(math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise KeyError(f"no value law for weight {name!r}")


def _prec(cdt):
    return lax.Precision.HIGHEST if cdt == jnp.float32 else None


def _mm(a, b, cdt):
    return jnp.matmul(a.astype(cdt), b.astype(cdt), precision=_prec(cdt))


def _norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _conv(u, w, b):
    """Causal depthwise conv: out_t = sum_i w[i] u_{t-K+1+i}, then SiLU.
    u: [n, L, C]; w: [K, C]."""
    K, L = w.shape[0], u.shape[1]
    pad = jnp.pad(u.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + L] * w[i].astype(jnp.float32) for i in range(K))
    return jax.nn.silu(out + b.astype(jnp.float32))


def logits(params, spec, tokens, cdt=jnp.float32):
    """tokens: [n, L] int32 -> logits [n, L, vocab_size] float32."""
    d, N, P = spec["d_model"], spec["d_state"], spec["headdim"]
    di = spec["expand"] * d
    H = di // P
    eps, V = spec["norm_epsilon"], spec["vocab_size"]
    emb = params["embed"][:V].astype(cdt)
    x = emb[tokens]
    n, L = tokens.shape

    def layer(x, p):
        m = p["blk0"]["mixer"]
        h = _norm(x, m["ln"], eps)
        z = _mm(h, m["w_z"], cdt).astype(jnp.float32)
        xs = _conv(_mm(h, m["w_x"], cdt), m["conv_x"], m["conv_bx"])
        Bm = _conv(_mm(h, m["w_B"], cdt), m["conv_B"], m["conv_bB"])
        Cm = _conv(_mm(h, m["w_C"], cdt), m["conv_C"], m["conv_bC"])
        xs = xs.astype(cdt).astype(jnp.float32).reshape(n, L, H, P)
        Bm = Bm.astype(cdt).astype(jnp.float32)
        Cm = Cm.astype(cdt).astype(jnp.float32)
        dt = jax.nn.softplus(_mm(h, m["w_dt"], cdt).astype(jnp.float32)
                             + m["dt_bias"])
        A = -jnp.exp(m["A_log"])

        def tick(state, inp):
            x_t, B_t, C_t, dt_t = inp          # [n,H,P] [n,N] [n,N] [n,H]
            state = (jnp.exp(dt_t * A)[:, :, None, None] * state
                     + (dt_t[:, :, None] * x_t)[..., None]
                     * B_t[:, None, None, :])
            y = jnp.einsum("nhpk,nk->nhp", state, C_t,
                           precision=lax.Precision.HIGHEST)
            return state, y

        state0 = jnp.zeros((n, H, P, N), jnp.float32)
        seq = (xs.transpose(1, 0, 2, 3), Bm.transpose(1, 0, 2),
               Cm.transpose(1, 0, 2), dt.transpose(1, 0, 2))
        _, y = lax.scan(tick, state0, seq)
        y = y.transpose(1, 0, 2, 3) + m["D"][:, None] * xs
        y = y.reshape(n, L, di) * jax.nn.silu(z)
        y = _norm(y.astype(cdt), m["gn"], eps)
        return x + _mm(y, m["out_proj"], cdt), None

    x, _ = lax.scan(layer, x, params["stages"][0])
    x = _norm(x, params["final_norm"], eps)
    return _mm(x, emb.T, cdt).astype(jnp.float32)
