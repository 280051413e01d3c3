"""Plain reference of a llama-style dense decoder: the forward pass over a
whole sequence in straightforward ``jax.numpy``, with no cache, no
batching of requests and no kernels.  It imports nothing of the program.

Per layer: pre-norm multi-head attention with rotary position embedding
(rotate-half form) and a causal softmax, then a pre-norm SiLU-gated MLP,
each added to the residual stream; a final norm, and logits against the
tied embedding.  RMS norms scale by ``1 + w``: the layout stores the
gain minus one.

Departures from the published MiniCPM-2B that this reference shares with
the program it checks (so that it computes what the program claims to):
no ``scale_emb`` on the embedding, no ``scale_depth / sqrt(layers)`` on
the residual branches, no ``hidden / (d_model / dim_model_base)`` before
the logits, and RMS norm epsilon 1e-6 against the published 1e-5.  The
configuration file lists them.

``cdt`` is the compute type: float32 runs every matmul at ``HIGHEST``
precision (the reference); bfloat16 casts weights and activations to
bfloat16 at default precision (the control, one precision below what
the configuration states).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# requests per reference call: one, since a whole [L, V] logits block of
# a 122,753-token vocabulary is half a gigabyte at L = 1024
BATCH = 1


def draw(name: str, key, shape, spec):
    """The value law of each weight, by the name of its leaf."""
    d, ff = spec["hidden_size"], spec["intermediate_size"]
    qd = spec["num_attention_heads"] * spec["head_dim"]

    def normal(std):
        return jax.random.normal(key, shape, jnp.float32) * std

    if name == "embed":
        rows = lax.broadcasted_iota(jnp.int32, shape, 0)
        # rows past the vocabulary pad the table; they stay 0
        return jnp.where(rows < spec["vocab_size"], normal(d ** -0.5), 0.0)
    if name in ("ln", "final_norm"):
        return normal(0.1)
    if name in ("wq", "wk", "wv", "w_gate", "w_up"):
        return normal(d ** -0.5)
    if name == "wo":
        return normal(qd ** -0.5)
    if name == "w_down":
        return normal(ff ** -0.5)
    raise KeyError(f"no value law for weight {name!r}")


def _mm(a, b, cdt):
    if cdt == jnp.float32:
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    return jnp.matmul(a.astype(cdt), b.astype(cdt))


def _norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """x: [L, H, hd]; position t rotates pair (i, i + hd/2) by
    t * theta^(-i / (hd/2))."""
    L, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def logits(params, spec, tokens, cdt=jnp.float32):
    """tokens: [n, L] int32 -> logits [n, L, vocab_size] float32."""
    return jax.vmap(lambda t: _logits(params, spec, t, cdt))(tokens)


def _logits(params, spec, tokens, cdt):
    H, KV, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"])
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    V = spec["vocab_size"]
    emb = params["embed"][:V].astype(cdt)
    x = emb[tokens]
    L = tokens.shape[0]
    causal = jnp.tril(jnp.ones((L, L), bool))

    def layer(x, p):
        a, f = p["blk0"]["attn"], p["blk0"]["ffn"]
        h = _norm(x, a["ln"], eps)
        q = _rope(_mm(h, a["wq"], cdt).reshape(L, H, hd), theta)
        k = _rope(_mm(h, a["wk"], cdt).reshape(L, KV, hd), theta)
        v = _mm(h, a["wv"], cdt).reshape(L, KV, hd)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        prec = lax.Precision.HIGHEST if cdt == jnp.float32 else None
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=prec)
        s = s.astype(jnp.float32) * hd ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(cdt)
        o = jnp.einsum("hqk,khd->qhd", w, v, precision=prec)
        x = x + _mm(o.reshape(L, H * hd), a["wo"], cdt)
        h = _norm(x, f["ln"], eps)
        g = jax.nn.silu(_mm(h, f["w_gate"], cdt).astype(jnp.float32))
        u = _mm(h, f["w_up"], cdt).astype(jnp.float32)
        x = x + _mm((g * u).astype(cdt), f["w_down"], cdt)
        return x, None

    x, _ = lax.scan(layer, x, params["stages"][0])
    x = _norm(x, params["final_norm"], eps)
    return _mm(x, emb.T, cdt).astype(jnp.float32)
