"""Plain reference of DeepSeek-V2 with its experts cut to one chip's
share: the forward pass over a whole sequence in straightforward
``jax.numpy``, with no cache, no batching of requests and no kernels.  It
imports nothing of the program.

Per layer: pre-norm multi-head latent attention in its expanded form (no
q LoRA: q = h Wq, split into a 128-wide part and a 64-wide rotary part;
the latent c = norm(h Wdkv[:, :512]) and one shared rotary key
h Wdkv[:, 512:]; per head k = [c Wuk, k_rope], v = c Wuv), YaRN rotary
frequencies, softmax scale q_head_dim^-0.5 times mscale(40, 0.707)^2 and
a causal softmax; then a pre-norm SiLU-gated MLP in the first
``first_k_dense_replace`` layers and, after them, a mixture of experts:
the gate's softmax over all ``published.n_routed_experts`` logits
(float32 products), greedy top-``num_experts_per_tok`` weights, not
renormalised where ``norm_topk_prob`` is false, times
``routed_scaling_factor``; the held experts (ids below
``n_routed_experts``) add their weighted SiLU-gated outputs, the others
nothing, and the shared experts (one SiLU-gated MLP of width
``n_shared_experts x moe_intermediate_size``) add theirs for every
token.  A final norm and logits against the untied head.  RMS norms scale
by ``1 + w``: the layout stores the gain minus one.

Departures from the published DeepSeek-V2-Lite, shared with the program
it checks: the rotation pairs dimension i with i + 32 of the 64 rotary
dimensions, where the published model pairs adjacent ones (the same
model under a fixed permutation of the rotary columns of its q and kv_a
projections); the experts are this chip's share, as the configuration
file states.

The YaRN parameters are read from the file's flat ``rope_scaling_<key>``
copies: the check hands a reference only top-level numbers and strings.

``cdt`` is the compute type: float32 runs every matmul at ``HIGHEST``
precision (the reference); bfloat16 casts weights and activations to
bfloat16 at default precision (the controls).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# requests per reference call: one, since a whole [L, V] logits block of
# a 102,400-token vocabulary is 0.8 GB at L = 2048
BATCH = 1

NORMS = ("ln", "final_norm", "kv_norm")


def draw(name: str, key, shape, spec):
    """The value law of each weight, by the name of its leaf: norm gains
    1 + N(0, 0.1), every matrix normal with std 1/sqrt(fan-in) (its
    second-to-last axis; layers and experts stack in front), the
    embedding with std 1/sqrt(hidden_size)."""
    if name in NORMS:
        return jax.random.normal(key, shape, jnp.float32) * 0.1
    std = spec["hidden_size"] ** -0.5 if name == "embed" else shape[-2] ** -0.5
    w = jax.random.normal(key, shape, jnp.float32) * std
    if name in ("embed", "lm_head"):
        # rows (columns of the head) past the vocabulary pad the table;
        # they stay 0
        axis = 0 if name == "embed" else 1
        ids = lax.broadcasted_iota(jnp.int32, shape, axis)
        w = jnp.where(ids < spec["vocab_size"], w, 0.0)
    return w


def _mm(a, b, cdt):
    if cdt == jnp.float32:
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    return jnp.matmul(a.astype(cdt), b.astype(cdt))


def _norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(spec):
    """DeepSeek-V2's YaRN frequencies (``DeepseekV2YarnRotaryEmbedding``)."""
    ys = {k: spec[f"rope_scaling_{k}"] for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")}
    dim, base = spec["qk_rope_head_dim"], spec["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / ys["factor"]

    def corr(rot):
        return (dim * math.log(ys["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                 # 1: the original (extrapolated) one
    if _yarn_mscale(ys["factor"], ys["mscale"]) != _yarn_mscale(
            ys["factor"], ys["mscale_all_dim"]):
        # DeepSeek-V2 then scales cos and sin by the ratio; 1 for V2-Lite
        raise ValueError("YaRN with mscale != mscale_all_dim")
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def _rope(x, inv):
    """x: [L, H, dim]; position t rotates pair (i, i + dim/2) by
    t * inv[i]."""
    L, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _glu(h, w_gate, w_up, w_down, cdt):
    g = jax.nn.silu(_mm(h, w_gate, cdt).astype(jnp.float32))
    u = _mm(h, w_up, cdt).astype(jnp.float32)
    return _mm((g * u).astype(cdt), w_down, cdt)


def logits(params, spec, tokens, cdt=jnp.float32):
    """tokens: [n, L] int32 -> logits [n, L, vocab_size] float32."""
    return jax.vmap(lambda t: _logits(params, spec, t, cdt))(tokens)


def _logits(params, spec, tokens, cdt):
    H, r = spec["num_attention_heads"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    eps, V = spec["rms_norm_eps"], spec["vocab_size"]
    K, held = spec["num_experts_per_tok"], spec["n_routed_experts"]
    scale = ((dn + dr) ** -0.5 * _yarn_mscale(
        spec["rope_scaling_factor"], spec["rope_scaling_mscale_all_dim"]) ** 2)
    inv = _inv_freq(spec)
    prec = lax.Precision.HIGHEST if cdt == jnp.float32 else None
    x = params["embed"][:V].astype(cdt)[tokens]
    L = tokens.shape[0]
    causal = jnp.tril(jnp.ones((L, L), bool))

    def attention(x, a):
        h = _norm(x, a["ln"], eps)
        q = _mm(h, a["wq"], cdt).reshape(L, H, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv)
        dkv = _mm(h, a["w_dkv"], cdt)
        c = _norm(dkv[:, :r], a["kv_norm"], eps)
        k_rope = _rope(dkv[:, None, r:], inv)[:, 0]
        k_nope = _mm(c, a["w_uk"], cdt).reshape(L, H, dn)
        v = _mm(c, a["w_uv"], cdt).reshape(L, H, dv)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=prec)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_rope, precision=prec))
        s = jnp.where(causal[None], s.astype(jnp.float32) * scale, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(cdt)
        o = jnp.einsum("hqk,khd->qhd", w, v, precision=prec)
        return x + _mm(o.reshape(L, H * dv), a["wo"], cdt)

    def dense_layer(x, p):
        p = p["blk0"]
        x = attention(x, p["attn"])
        f = p["ffn"]
        h = _norm(x, f["ln"], eps)
        return x + _glu(h, f["w_gate"], f["w_up"], f["w_down"], cdt), None

    def moe_layer(x, p):
        p = p["blk0"]
        x = attention(x, p["attn"])
        f = p["ffn"]
        h = _norm(x, f["ln"], eps)
        gate = _mm(h.astype(jnp.float32), f["router"], jnp.float32) \
            if cdt == jnp.float32 else _mm(h, f["router"], cdt)
        probs = jax.nn.softmax(gate.astype(jnp.float32), axis=-1)
        top_p, top_i = lax.top_k(probs, K)
        if spec["norm_topk_prob"]:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        top_p = top_p * spec["routed_scaling_factor"]
        y = _glu(h, f["shared"]["w_gate"], f["shared"]["w_up"],
                 f["shared"]["w_down"], cdt).astype(jnp.float32)
        for e in range(held):
            w = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)
            out = _glu(h, f["w_gate"][e], f["w_up"][e], f["w_down"][e], cdt)
            y = y + w[:, None] * out.astype(jnp.float32)
        return x + y.astype(x.dtype), None

    dense_stage, moe_stage = params["stages"]
    x, _ = lax.scan(dense_layer, x, dense_stage)
    x, _ = lax.scan(moe_layer, x, moe_stage)
    x = _norm(x, params["final_norm"], eps)
    return _mm(x, params["lm_head"][:, :V], cdt).astype(jnp.float32)
