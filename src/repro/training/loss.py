"""Sequence-chunked cross-entropy.

Materializing [B, S, V] logits for V up to 262k is the dominant activation
cost; we instead scan over sequence chunks with a rematerialized body so
peak logits memory is [B, chunk, V] and the backward pass recomputes each
chunk's logits from (hidden, lm_head).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def chunked_cross_entropy(hidden, w_head, labels, mask, chunk: int = 512):
    """hidden: [B,S,d]; w_head: [d,V]; labels/mask: [B,S].

    Returns (mean_nll, n_tokens)."""
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nchunk = hidden.shape[1] // chunk
    hs = hidden.reshape(B, nchunk, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nchunk, chunk).transpose(1, 0, 2)
    ms = mask.reshape(B, nchunk, chunk).transpose(1, 0, 2)

    def body(acc, inp):
        h, lab, m = inp
        logits = (h @ w_head).astype(jnp.float32)            # [B,C,V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll = jnp.sum((lse - tgt) * m.astype(jnp.float32))
        return acc + nll, None

    body = jax.checkpoint(body, prevent_cse=False)
    total, _ = lax.scan(body, jnp.float32(0.0), (hs, ls, ms))
    n_tok = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return total / n_tok, n_tok
