"""Core layers: norms, RoPE (plain and YaRN), GQA attention (full /
sliding-window), MLA, gated FFN, and MoE dispatch over the experts a chip
holds.

Every layer has a full-sequence path (train / prefill) and a single-token
decode path operating on an explicit KV/state cache, so the serving engine
and the training loop share one parameterization.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRN
from repro.models.grouped_gemm import grouped_gemm

# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def _act(name: str):
    return jax.nn.gelu if name == "gelu" else jax.nn.silu


def rope(x, positions, theta: float, inv_freq=None):
    """Rotary embedding.  x: [..., L, H, hd]; positions: [..., L].
    Dimension i pairs with i + hd/2 and turns at ``inv_freq[i]``
    (default theta^(-i / (hd/2)))."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
             if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    ang = positions[..., None].astype(jnp.float32) * freqs          # [..., L, half]
    cos = jnp.cos(ang)[..., None, :]                                # [..., L, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, y: YaRN) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies for a rotary dimension ``dim``: the
    interpolated frequencies (theta^(-2i/dim) / factor) for the slow
    dimensions, the original ones for the fast, and a linear ramp between
    the dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    the original context."""
    if _yarn_mscale(y.factor, y.mscale) != _yarn_mscale(y.factor,
                                                        y.mscale_all_dim):
        # DeepSeek-V2 scales cos and sin by their ratio; it is 1 for
        # every published DeepSeek-V2 configuration, and not applied here
        raise NotImplementedError("YaRN with mscale != mscale_all_dim")
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns(rot):
        return (dim * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns(y.beta_fast)), 0)
    high = min(math.ceil(turns(y.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / y.factor * ramp + extra * (1 - ramp)).astype(np.float32)


def mla_softmax_scale(m: MLAConfig) -> float:
    """q_head_dim^-0.5, times mscale(factor, mscale_all_dim)^2 under YaRN."""
    s = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    if m.yarn is not None and m.yarn.mscale_all_dim:
        s *= _yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim) ** 2
    return s


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# GQA attention (mixer: "full" | "window")
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype):
    d, qd, kvd = cfg.d_model, cfg.attn_q_dim, cfg.attn_kv_dim
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "ln": jnp.zeros((d,), dtype),
        "wq": _normal(ks[0], (d, qd), std, dtype),
        "wk": _normal(ks[1], (d, kvd), std, dtype),
        "wv": _normal(ks[2], (d, kvd), std, dtype),
        "wo": _normal(ks[3], (qd, d), qd ** -0.5, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def _qkv(p, cfg: ModelConfig, x, positions):
    B, L, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, L, H, hd)
    k = (x @ p["wk"]).reshape(B, L, KV, hd)
    v = (x @ p["wv"]).reshape(B, L, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, softcap: Optional[float] = None):
    """q: [B,Lq,H,hd], k/v: [B,Lk,KV,hd], mask: [B or 1, Lq, Lk] bool."""
    B, Lq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Lq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores *= hd ** -0.5
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Lq, H * hd)


def _sdpa_chunked(q, k, v, window: Optional[int],
                  softcap: Optional[float], chunk: int = 512):
    """Causal SDPA scanned over query chunks so peak score memory is
    [B, H, chunk, Lk] instead of [B, H, Lq, Lk] (flash-attention-style
    blocking at the XLA level; the Pallas kernel tiles further on-chip)."""
    B, Lq, H, hd = q.shape
    if Lq <= chunk:
        return _sdpa(q, k, v, causal_mask(Lq, Lq, window), softcap)
    # a ragged tail pads the queries only: padded rows are dropped below
    n = -(-Lq // chunk)
    q = jnp.pad(q, ((0, 0), (0, n * chunk - Lq), (0, 0), (0, 0)))
    qs = q.reshape(B, n, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    offs = jnp.arange(n) * chunk

    def body(_, inp):
        qc, off = inp
        qi = off + jnp.arange(chunk)[:, None]
        ki = jnp.arange(Lq)[None, :]
        m = ki <= qi
        if window is not None:
            m &= ki > qi - window
        return None, _sdpa(qc, k, v, m[None], softcap)

    # checkpoint the chunk body: backward recomputes each chunk's scores
    # instead of stacking [n_chunks, ..., Lk] fp32 probs across the scan
    _, out = lax.scan(jax.checkpoint(body, prevent_cse=False), None,
                      (qs, offs))
    return out.transpose(1, 0, 2, 3).reshape(B, n * chunk, H * hd)[:, :Lq]


def causal_mask(Lq: int, Lk: int, window: Optional[int] = None,
                offset: int = 0):
    """[1, Lq, Lk] bool.  offset = number of earlier tokens already in k."""
    qi = jnp.arange(Lq)[:, None] + offset
    ki = jnp.arange(Lk)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m[None]


def attn_forward(p, cfg: ModelConfig, x, positions, window: Optional[int]):
    """Full-sequence causal attention (train / prefill).

    Returns (y, (k, v)) so prefill can populate the cache."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, positions)
    y = _sdpa_chunked(q, k, v, window, cfg.logit_softcap)
    return y @ p["wo"], (k, v)


def attn_decode(p, cfg: ModelConfig, x, cache_k, cache_v, pos,
                window: Optional[int]):
    """One-token decode.  x: [B,1,d]; cache_k/v: [B,S,KV,hd]; pos: [B].

    For window layers the cache is a ring buffer of size min(S, window)
    written at ``pos % S``; RoPE is applied pre-cache so ring order is
    irrelevant to scores.

    Attention reads the cache with the new token selected in at its slot
    (``select_token``, which fuses into the score and value reads); the
    cache itself is not written here.  Returns (y, (k_new, v_new)), the
    token's rows [B,1,KV,hd], which ``model.decode_step`` writes with
    ``write_token_rows`` once per stage, after all its layers have read
    the cache: B row writes in place, not a rewrite of the whole cache.
    """
    S = cache_k.shape[1]
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k_new, v_new = _qkv(p, cfg, h, pos[:, None])
    # valid slots: ring full once pos >= S-1, else slots <= pos
    valid = (jnp.arange(S)[None, :] <= pos[:, None]) | (pos[:, None] >= S)
    y = _sdpa(q, select_token(cache_k, k_new, pos),
              select_token(cache_v, v_new, pos), valid[:, None, :],
              cfg.logit_softcap)
    return y @ p["wo"], (k_new, v_new)


def select_token(cache, new, pos):
    """``cache`` [B,S,...] with row b's slot ``pos[b] % S`` replaced by
    ``new`` [B,1,...]: a masked select, which the attention reads fuse,
    so the selected cache is never materialized."""
    S = cache.shape[1]
    hit = (jnp.arange(S)[None, :] == (pos % S)[:, None])[
        (...,) + (None,) * (cache.ndim - 2)]
    return jnp.where(hit, new, cache)


def write_token_rows(cache, new, pos):
    """Write ``new`` [R,B,1,...] into a stage's stacked cache ``cache``
    [R,B,S,...] (layers, slots, positions, then any trailing shape) at
    row b's slot ``pos[b] % S``, in place: one dynamic_update_slice per
    row, which writes B tokens and keeps the cache's layout (a scatter
    made the TPU compiler relay out and copy the whole cache)."""
    B, S = cache.shape[1], cache.shape[2]
    slot = pos % S
    for b in range(B):
        cache = lax.dynamic_update_slice(
            cache, lax.slice_in_dim(new, b, b + 1, axis=1),
            (0, b, slot[b]) + (0,) * (cache.ndim - 3))
    return cache


def attn_chunk(p, cfg: ModelConfig, x, cache_k, cache_v, pos0,
               window: Optional[int]):
    """Chunked prefill: extend a LINEAR (slot == position) KV cache by C
    prompt tokens starting at ``pos0``.  x: [B,C,d]; cache_k/v:
    [B,S,KV,hd] with S >= pos0 + C; pos0: [B].

    Unlike the decode ring buffer, slots here ARE absolute positions (the
    staging cache never wraps during a prefill), so the causal/window
    mask is a direct position comparison and earlier chunks' keys stay
    addressable for this chunk's queries.  Ring conversion happens once,
    at splice time (``model.ring_convert_cache``)."""
    B, C, _ = x.shape
    S = cache_k.shape[1]
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    positions = pos0[:, None] + jnp.arange(C)[None, :]
    q, k_new, v_new = _qkv(p, cfg, h, positions)

    def put(ck, kn, p0):
        return lax.dynamic_update_slice(ck, kn, (p0, 0, 0))

    cache_k = jax.vmap(put)(cache_k, k_new, pos0)
    cache_v = jax.vmap(put)(cache_v, v_new, pos0)
    slot = jnp.arange(S)[None, None, :]
    qpos = positions[:, :, None]
    mask = slot <= qpos                    # [B, C, S]
    if window is not None:
        mask &= slot > qpos - window
    y = _sdpa(q, cache_k, cache_v, mask, cfg.logit_softcap)
    return y @ p["wo"], (cache_k, cache_v)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype):
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 5)
    std = d ** -0.5
    return {
        "ln": jnp.zeros((d,), dtype),
        "wq": _normal(ks[0], (d, H * (m.nope_head_dim + m.rope_head_dim)), std, dtype),
        "w_dkv": _normal(ks[1], (d, m.kv_lora_rank + m.rope_head_dim), std, dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": _normal(ks[2], (m.kv_lora_rank, H * m.nope_head_dim),
                        m.kv_lora_rank ** -0.5, dtype),
        "w_uv": _normal(ks[3], (m.kv_lora_rank, H * m.v_head_dim),
                        m.kv_lora_rank ** -0.5, dtype),
        "wo": _normal(ks[4], (H * m.v_head_dim, d),
                      (H * m.v_head_dim) ** -0.5, dtype),
    }


def _mla_q_and_latent(p, cfg: ModelConfig, h, positions):
    """Queries (no q LoRA) and the latent: (q_nope, q_rope, c_kv, k_rope),
    the rope parts turned at the YaRN frequencies where the model has
    them.  The rotation pairs dimension i with i + rope_dim/2, where
    DeepSeek-V2 pairs adjacent dimensions: the same model under a fixed
    permutation of the rope columns of ``wq`` and ``w_dkv``."""
    m: MLAConfig = cfg.mla
    B, L, _ = h.shape
    H = cfg.num_heads
    inv = (None if m.yarn is None
           else yarn_inv_freq(m.rope_head_dim, cfg.rope_theta, m.yarn))
    q = (h @ p["wq"]).reshape(B, L, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = rope(q_rope, positions, cfg.rope_theta, inv)
    dkv = h @ p["w_dkv"]
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                  inv)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


@jax.named_scope("mla")
def mla_forward(p, cfg: ModelConfig, x, positions, chunk: int = 512):
    """Full-sequence MLA (expanded form), scanned over query chunks so peak
    score memory is [B, H, chunk, L]; a ragged tail pads the queries only.
    Returns (y, (c_kv, k_rope))."""
    m: MLAConfig = cfg.mla
    B, L, _ = x.shape
    H = cfg.num_heads
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q_nope, q_rope, c_kv, k_rope = _mla_q_and_latent(p, cfg, h, positions)
    k_nope = (c_kv @ p["w_uk"]).reshape(B, L, H, m.nope_head_dim)
    v = (c_kv @ p["w_uv"]).reshape(B, L, H, m.v_head_dim)
    scale = mla_softmax_scale(m)

    def attend(qn, qr, mask):
        scores = (jnp.einsum("bqhd,bshd->bhqs", qn, k_nope)
                  + jnp.einsum("bqhd,bsd->bhqs", qr, k_rope)
                  ).astype(jnp.float32) * scale
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)

    if L <= chunk:
        y = attend(q_nope, q_rope, causal_mask(L, L))
    else:
        n = -(-L // chunk)
        pad = ((0, 0), (0, n * chunk - L), (0, 0), (0, 0))
        qn = jnp.pad(q_nope, pad).reshape(B, n, chunk, H, -1) \
            .transpose(1, 0, 2, 3, 4)
        qr = jnp.pad(q_rope, pad).reshape(B, n, chunk, H, -1) \
            .transpose(1, 0, 2, 3, 4)
        offs = jnp.arange(n) * chunk

        def body(_, inp):
            qnc, qrc, off = inp
            qi = off + jnp.arange(chunk)[:, None]
            mask = (jnp.arange(L)[None, :] <= qi)[None]
            return None, attend(qnc, qrc, mask)

        _, y = lax.scan(jax.checkpoint(body, prevent_cse=False), None,
                        (qn, qr, offs))
        y = y.transpose(1, 0, 2, 3, 4).reshape(
            B, n * chunk, H, m.v_head_dim)[:, :L]
    y = y.reshape(B, L, H * m.v_head_dim)
    return y @ p["wo"], (c_kv, k_rope)


@jax.named_scope("mla")
def mla_decode(p, cfg: ModelConfig, x, cache_ckv, cache_krope, pos):
    """One-token MLA decode with matrix absorption: scores and values are
    computed directly in the compressed latent space, so per-step cost is
    O(L * (kv_lora + rope_dim)) instead of O(L * H * head_dim).

    cache_ckv: [B,S,kv_lora]; cache_krope: [B,S,rope_dim]; pos: [B].
    Attention reads the cache with the new token selected in at its slot
    (``select_token``).  Returns (y, (c_new [B,1,kv_lora], kr_new
    [B,1,rope_dim])), the token's latent rows, which ``model.decode_step``
    writes as it writes ``attn_decode``'s.
    """
    m: MLAConfig = cfg.mla
    S = cache_ckv.shape[1]
    B, H = x.shape[0], cfg.num_heads
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q_nope, q_rope, c_new, kr_new = _mla_q_and_latent(p, cfg, h, pos[:, None])
    cache_ckv = select_token(cache_ckv, c_new, pos)
    cache_krope = select_token(cache_krope, kr_new, pos)
    # absorb w_uk into q:  q_lat[b,h,r] = sum_d q_nope[b,h,d] * w_uk[r, h*d]
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.nope_head_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    scale = mla_softmax_scale(m)
    scores = (jnp.einsum("bhr,bsr->bhs", q_lat, cache_ckv)
              + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0], cache_krope)
              ).astype(jnp.float32) * scale
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhs,bsr->bhr", probs, cache_ckv)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    y = jnp.einsum("bhr,rhd->bhd", out_lat, w_uv).reshape(B, 1, H * m.v_head_dim)
    return y @ p["wo"], (c_new, kr_new)


# ---------------------------------------------------------------------------
# Dense gated FFN
# ---------------------------------------------------------------------------

def init_ffn(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "ln": jnp.zeros((d,), dtype),
        "w_gate": _normal(ks[0], (d, ff), d ** -0.5, dtype),
        "w_up": _normal(ks[1], (d, ff), d ** -0.5, dtype),
        "w_down": _normal(ks[2], (ff, d), ff ** -0.5, dtype),
    }


def ffn_forward(p, cfg: ModelConfig, x):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    act = _act(cfg.act)
    z = act(h @ p["w_gate"]) * (h @ p["w_up"])
    return z @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE: the router over every expert, the experts this chip holds
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype):
    """The router keeps all ``num_experts`` outputs; the expert weights are
    the held ones' (ids [0, held))."""
    mo: MoEConfig = cfg.moe
    d, E, ff = cfg.d_model, mo.num_experts, mo.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "ln": jnp.zeros((d,), dtype),
        "router": _normal(ks[0], (d, E), d ** -0.5, jnp.float32),
        "w_gate": _normal(ks[1], (mo.held, d, ff), d ** -0.5, dtype),
        "w_up": _normal(ks[2], (mo.held, d, ff), d ** -0.5, dtype),
        "w_down": _normal(ks[3], (mo.held, ff, d), ff ** -0.5, dtype),
    }
    if mo.num_shared:
        sh = init_ffn(ks[4], cfg, dtype, d_ff=mo.d_ff_shared)
        del sh["ln"]  # shared experts consume the same normed input
        p["shared"] = sh
    return p


def _route(p, mo: MoEConfig, xt):
    """Softmax over every expert's logit, greedy top-k: (probs [T, E],
    weights [T, K], expert ids [T, K]); the weights renormalised to sum
    to 1 where ``mo.norm_topk_prob``.  The logits are float32 products,
    as DeepSeek-V2 computes its gate: at the TPU's default precision
    bfloat16 products would change which experts a token picks."""
    logits = jnp.matmul(xt.astype(jnp.float32), p["router"],
                        precision=lax.Precision.HIGHEST)       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, mo.top_k)                  # [T, K]
    if mo.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return probs, top_p, top_i


def _aux_loss(probs, top_i, E: int):
    """Switch-style load-balancing loss."""
    frac_tokens = jnp.mean(jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                           axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)


def _moe_held(p, cfg: ModelConfig, xt, act, one_token: bool):
    """The held experts' part of the routed result for tokens xt [T, d],
    with no token dropped: what a token gets does not depend on what else
    is in the batch.  Picks of experts this chip does not hold add
    nothing (their chips add them).

    - ``one_token`` (a decode step, a few tokens): every held expert
      computes every token, weighted by its routing weight or 0, so each
      held expert's weights are read once, the least a step reads;
    - otherwise (a prompt): the picks that fall on held experts, sorted by
      expert, go through one grouped matmul per weight (``grouped_gemm``:
      ``lax.ragged_dot``, a grouped-matmul kernel on a TPU); the other
      picks sort past the last group.

    Returns (y [T, d], aux_loss, held [T] int32: the token's picks that
    fell on a held expert).
    """
    mo: MoEConfig = cfg.moe
    T, d = xt.shape
    Eh, K = mo.held, mo.top_k
    probs, top_p, top_i = _route(p, mo, xt)
    on = top_i < Eh                                            # [T, K]
    w = jnp.where(on, top_p, 0.0)
    if one_token:
        w_te = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
            top_i, Eh, dtype=w.dtype))                         # [T, Eh]
        gate = jnp.einsum("td,edf->etf", xt, p["w_gate"])
        up = jnp.einsum("td,edf->etf", xt, p["w_up"])
        out = jnp.einsum("etf,efd->etd", act(gate) * up, p["w_down"])
        y = jnp.einsum("etd,te->td", out, w_te.astype(out.dtype))
    else:
        flat_e = jnp.where(on, top_i, Eh).reshape(-1)          # [M = T*K]
        order = jnp.argsort(flat_e)
        sizes = jnp.bincount(flat_e, length=Eh + 1)[:Eh].astype(jnp.int32)
        xs = jnp.take(xt, order // K, axis=0)                  # [M, d]
        gate = grouped_gemm(xs, p["w_gate"], sizes)
        up = grouped_gemm(xs, p["w_up"], sizes)
        out = grouped_gemm(act(gate) * up, p["w_down"], sizes)
        w_sorted = jnp.take(w.reshape(-1), order)
        live = jnp.arange(T * K) < jnp.sum(sizes)              # in a group
        out = jnp.where(live[:, None],
                        out * w_sorted[:, None].astype(out.dtype), 0.0)
        y = jnp.take(out, jnp.argsort(order), axis=0) \
            .reshape(T, K, d).sum(axis=1)
    held = jnp.sum(on, axis=-1, dtype=jnp.int32)
    return y, _aux_loss(probs, top_i, mo.num_experts), held


@jax.named_scope("moe")
def moe_forward(p, cfg: ModelConfig, x):
    """x: [B, L, d] -> (y, aux_loss, held [B, L] int32).

    The held experts' part is computed with no token dropped
    (``_moe_held``), plus the shared experts for every token.  ``held``
    counts each token's top-k picks that fell on an expert this chip
    holds.
    """
    B, L, d = x.shape
    act = _act(cfg.act)
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    y, aux, held = _moe_held(p, cfg, h.reshape(-1, d), act,
                             one_token=L == 1)
    held = held.reshape(B, L)
    y = y.reshape(B, L, d)
    if "shared" in p:
        sp = p["shared"]
        zs = act(h @ sp["w_gate"]) * (h @ sp["w_up"])
        y = y + zs @ sp["w_down"]
    return y, aux, held
