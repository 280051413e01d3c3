"""Core layers: norms, RoPE, GQA attention (full / sliding-window), MLA,
gated FFN, and sort-based MoE dispatch (ragged grouped GEMM).

Every layer has a full-sequence path (train / prefill) and a single-token
decode path operating on an explicit KV/state cache, so the serving engine
and the training loop share one parameterization.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.distributed.context import NULL_CTX, ShardCtx
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


def rope(x, positions, theta: float):
    """Rotary embedding.  x: [..., L, H, hd]; positions: [..., L]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # [..., L, half]
    cos = jnp.cos(ang)[..., None, :]                                # [..., L, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


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


def _qkv(p, cfg: ModelConfig, x, positions, ctx: ShardCtx,
         decode: bool = False):
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
    bspec = ctx.batch_spec_entry(B)
    kv_tp = ctx.enabled and KV % ctx.tp_size == 0
    if decode:
        # flash-decoding regime when KV heads don't divide the model axis:
        # replicate the one-token q/k/v, shard the cache on sequence, and
        # let the partial-softmax combine run as tiny all-reduces.
        if kv_tp:
            q = ctx.constraint(q, bspec, None, ctx.model_axis, None)
        else:
            q = ctx.constraint(q, bspec, None, None, None)
        return q, k, v
    if ctx.enabled:
        if H % ctx.tp_size == 0:
            q = ctx.constraint(q, bspec, None, ctx.model_axis, None)
        else:
            # H doesn't divide TP: head_dim-sharded q would force an
            # all-reduce of the full [*, Lq, Lk] score tensor per chunk
            # (observed 19.8 TB/step for musicgen prefill_32k).  Instead
            # replicate q and shard K/V on *sequence*: scores stay local
            # and only the softmax max/sum + output partials reduce.
            q = ctx.constraint(q, bspec, None, None, None)
        if kv_tp:
            k = ctx.constraint(k, bspec, None, ctx.model_axis, None)
            v = ctx.constraint(v, bspec, None, ctx.model_axis, None)
        else:
            seq = ctx.model_axis if L % ctx.tp_size == 0 else None
            k = ctx.constraint(k, bspec, seq, None, None)
            v = ctx.constraint(v, bspec, seq, None, None)
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


def attn_forward(p, cfg: ModelConfig, x, positions, window: Optional[int],
                 ctx: ShardCtx = NULL_CTX):
    """Full-sequence causal attention (train / prefill).

    Returns (y, (k, v)) so prefill can populate the cache."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, positions, ctx)
    y = _sdpa_chunked(q, k, v, window, cfg.logit_softcap)
    return y @ p["wo"], (k, v)


def attn_decode(p, cfg: ModelConfig, x, cache_k, cache_v, pos,
                window: Optional[int], ctx: ShardCtx = NULL_CTX):
    """One-token decode.  x: [B,1,d]; cache_k/v: [B,S,KV,hd]; pos: [B].

    For window layers the cache is a ring buffer of size min(S, window)
    written at ``pos % S``; RoPE is applied pre-cache so ring order is
    irrelevant to scores.

    Attention reads the cache with the new token selected in at its slot
    (``select_token``, which fuses into the score and value reads).
    Returns (y, (k_new, v_new)), the token's rows [B,1,KV,hd], which
    ``model.decode_step`` stores in one of two ways:

    - under a mesh (``ctx.enabled``: the sharded dry-run, whose cache may
      be sharded on S), ``select_token`` over the whole layer slice, which
      is written back: elementwise along S, so GSPMD keeps it local;
    - with no mesh (every served engine), ``write_token_rows`` once per
      stage after all its layers have read the cache: B row writes in
      place, instead of a rewrite of the whole cache every layer.
    """
    S = cache_k.shape[1]
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k_new, v_new = _qkv(p, cfg, h, pos[:, None], ctx, decode=True)
    # valid slots: ring full once pos >= S-1, else slots <= pos
    valid = (jnp.arange(S)[None, :] <= pos[:, None]) | (pos[:, None] >= S)
    y = _sdpa(q, select_token(cache_k, k_new, pos),
              select_token(cache_v, v_new, pos), valid[:, None, :],
              cfg.logit_softcap)
    return y @ p["wo"], (k_new, v_new)


def select_token(cache, new, pos):
    """``cache`` [B,S,KV,hd] with row b's slot ``pos[b] % S`` replaced by
    ``new`` [B,1,KV,hd]: a masked select over the whole cache.  It is
    elementwise along a (possibly sequence-sharded) S, so GSPMD never falls
    back to its "involuntary full rematerialization" replication path."""
    S = cache.shape[1]
    hit = (jnp.arange(S)[None, :] == (pos % S)[:, None])[..., None, None]
    return jnp.where(hit, new, cache)


def write_token_rows(cache, new, pos):
    """Write ``new`` [..., B,1,KV,hd] into ``cache`` [..., B,S,KV,hd] at
    row b's slot ``pos[b] % S``, in place: one dynamic_update_slice per
    row, which writes B tokens and keeps the cache's layout (a scatter
    made the TPU compiler relay out and copy the whole cache)."""
    lead = cache.ndim - 4
    B, S = cache.shape[lead], cache.shape[lead + 1]
    slot = pos % S
    for b in range(B):
        cache = lax.dynamic_update_slice(
            cache, lax.slice_in_dim(new, b, b + 1, axis=lead),
            (0,) * lead + (b, slot[b], 0, 0))
    return cache


def attn_chunk(p, cfg: ModelConfig, x, cache_k, cache_v, pos0,
               window: Optional[int], ctx: ShardCtx = NULL_CTX):
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
    q, k_new, v_new = _qkv(p, cfg, h, positions, ctx)

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
    m: MLAConfig = cfg.mla
    B, L, _ = h.shape
    H = cfg.num_heads
    q = (h @ p["wq"]).reshape(B, L, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    dkv = h @ p["w_dkv"]
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(p, cfg: ModelConfig, x, positions, ctx: ShardCtx = NULL_CTX,
                chunk: int = 512):
    """Full-sequence MLA (expanded form), scanned over query chunks so peak
    score memory is [B, H, chunk, L].  Returns (y, (c_kv, k_rope))."""
    m: MLAConfig = cfg.mla
    B, L, _ = x.shape
    H = cfg.num_heads
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q_nope, q_rope, c_kv, k_rope = _mla_q_and_latent(p, cfg, h, positions)
    k_nope = (c_kv @ p["w_uk"]).reshape(B, L, H, m.nope_head_dim)
    v = (c_kv @ p["w_uv"]).reshape(B, L, H, m.v_head_dim)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    bspec = ctx.batch_spec_entry(B)
    hspec = ctx.heads_spec(H, m.nope_head_dim)
    if hspec is not None:
        q_nope = ctx.constraint(q_nope, bspec, None, *hspec)
        k_nope = ctx.constraint(k_nope, bspec, None, *hspec)
        v = ctx.constraint(v, bspec, None, *hspec)

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
        assert L % chunk == 0, (L, chunk)
        n = L // chunk
        qn = q_nope.reshape(B, n, chunk, H, -1).transpose(1, 0, 2, 3, 4)
        qr = q_rope.reshape(B, n, chunk, H, -1).transpose(1, 0, 2, 3, 4)
        offs = jnp.arange(n) * chunk

        def body(_, inp):
            qnc, qrc, off = inp
            qi = off + jnp.arange(chunk)[:, None]
            mask = (jnp.arange(L)[None, :] <= qi)[None]
            return None, attend(qnc, qrc, mask)

        _, y = lax.scan(jax.checkpoint(body, prevent_cse=False), None,
                        (qn, qr, offs))
        y = y.transpose(1, 0, 2, 3, 4).reshape(B, L, H, m.v_head_dim)
    y = y.reshape(B, L, H * m.v_head_dim)
    return y @ p["wo"], (c_kv, k_rope)


def mla_decode(p, cfg: ModelConfig, x, cache_ckv, cache_krope, pos,
               ctx: ShardCtx = NULL_CTX):
    """One-token MLA decode with matrix absorption: scores and values are
    computed directly in the compressed latent space, so per-step cost is
    O(L * (kv_lora + rope_dim)) instead of O(L * H * head_dim).

    cache_ckv: [B,S,kv_lora]; cache_krope: [B,S,rope_dim]; pos: [B].
    """
    m: MLAConfig = cfg.mla
    B, S = cache_ckv.shape[0], cache_ckv.shape[1]
    H = cfg.num_heads
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q_nope, q_rope, c_new, kr_new = _mla_q_and_latent(p, cfg, h, pos[:, None])
    hit = (jnp.arange(S)[None, :] == pos[:, None])[..., None]
    cache_ckv = jnp.where(hit, c_new[:, 0][:, None], cache_ckv)
    cache_krope = jnp.where(hit, kr_new[:, 0][:, None], cache_krope)
    # absorb w_uk into q:  q_lat[b,h,r] = sum_d q_nope[b,h,d] * w_uk[r, h*d]
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.nope_head_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    scores = (jnp.einsum("bhr,bsr->bhs", q_lat, cache_ckv)
              + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0], cache_krope)
              ).astype(jnp.float32) * scale
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhs,bsr->bhr", probs, cache_ckv)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    y = jnp.einsum("bhr,rhd->bhd", out_lat, w_uv).reshape(B, 1, H * m.v_head_dim)
    return y @ p["wo"], (cache_ckv, cache_krope)


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


def ffn_forward(p, cfg: ModelConfig, x, ctx: ShardCtx = NULL_CTX):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    act = _act(cfg.act)
    z = act(h @ p["w_gate"]) * (h @ p["w_up"])
    z = ctx.constraint(z, ctx.batch_spec_entry(x.shape[0]), None,
                       ctx.model_axis_if_divides(z.shape[-1]))
    return z @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (sort-based dispatch + ragged grouped GEMM)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype):
    mo: MoEConfig = cfg.moe
    d, E, ff = cfg.d_model, mo.num_experts, mo.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "ln": jnp.zeros((d,), dtype),
        "router": _normal(ks[0], (d, E), d ** -0.5, jnp.float32),
        "w_gate": _normal(ks[1], (E, d, ff), d ** -0.5, dtype),
        "w_up": _normal(ks[2], (E, d, ff), d ** -0.5, dtype),
        "w_down": _normal(ks[3], (E, ff, d), ff ** -0.5, dtype),
    }
    if mo.num_shared:
        sh = init_ffn(ks[4], cfg, dtype, d_ff=mo.d_ff_shared)
        del sh["ln"]  # shared experts consume the same normed input
        p["shared"] = sh
    return p


def moe_capacity(T: int, K: int, E: int, cf: float) -> int:
    return int(min(T * K, max(-(-T * K * cf // E), 16)))


def _moe_local(p, cfg: ModelConfig, xt, act, axis_name: Optional[str] = None):
    """Sort + capacity-dispatch MoE over local tokens xt [T, d].

    Tokens are sorted by expert and scattered into [E, C, d] slots
    (C = capacity per expert); expert GEMMs are batched einsums.  This is
    the GShard/MaxText formulation: peak memory is O(T*K*cf*d) and the
    XLA graph contains no data-dependent dense expansions — unlike
    ``lax.ragged_dot``, whose one-hot decomposition materializes
    [E, T*K, d] (observed 640 GB/device on deepseek-v2-lite train_4k).
    Tokens beyond capacity are dropped (standard; the aux loss keeps
    routing balanced so drops are rare at cf=2).

    Returns (y [T,d], aux_loss).  When ``axis_name`` is given the expert
    ff dims are sharded across it and the down-projection is psummed
    (tensor parallel inside shard_map).
    """
    mo: MoEConfig = cfg.moe
    T, d = xt.shape
    E, K = mo.num_experts, mo.top_k
    C = moe_capacity(T, K, E, mo.capacity_factor)
    logits = (xt.astype(jnp.float32) @ p["router"])            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, K)                         # [T, K]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_i.reshape(-1)                                 # [M = T*K]
    M = T * K
    order = jnp.argsort(flat_e)
    e_sorted = jnp.take(flat_e, order)
    token_of = order // K
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(group_sizes)[:-1]])
    # gather-based dispatch: slot s of expert e reads sorted row
    # starts[e] + s (scatter-free — XLA's bf16->f32 scatter normalization
    # would otherwise materialize fp32 [E,C,d] buffers)
    slot_rows = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    slot_valid = jnp.arange(C, dtype=jnp.int32)[None, :] < group_sizes[:, None]
    slot_rows = jnp.minimum(slot_rows, M - 1)
    xs = jnp.take(xt, token_of, axis=0)                        # [M, d]
    slots = jnp.take(xs, slot_rows.reshape(-1), axis=0) \
        .reshape(E, C, d) * slot_valid[..., None].astype(xt.dtype)
    gate = jnp.einsum("ecd,edf->ecf", slots, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", slots, p["w_up"])
    hidden = act(gate) * up
    out_slots = jnp.einsum("ecf,efd->ecd", hidden, p["w_down"])
    if axis_name is not None:
        out_slots = lax.psum(out_slots, axis_name)
    # combine: inverse-permutation gather back to [T, K, d], weighted sum
    pos = jnp.arange(M, dtype=jnp.int32) - jnp.take(starts, e_sorted)
    keep = pos < C
    pos_c = jnp.where(keep, pos, C - 1)
    out_sorted = out_slots[e_sorted, pos_c] \
        * keep[:, None].astype(out_slots.dtype)                # [M, d]
    inv_order = jnp.argsort(order)
    out_tk = jnp.take(out_sorted, inv_order, axis=0).reshape(T, K, d)
    y = jnp.einsum("tkd,tk->td", out_tk, top_p.astype(out_tk.dtype))
    # Switch-style load-balancing aux loss
    frac_tokens = jnp.mean(jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                           axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y, aux


def moe_forward(p, cfg: ModelConfig, x, ctx: ShardCtx = NULL_CTX):
    """x: [B, L, d] -> (y, aux_loss).

    With an active mesh the dispatch runs under shard_map: tokens stay
    local to their (pod, data) shard (routing is per-token), expert ff
    dims are TP-sharded over the model axis, and only the O(T_local x d)
    down-projection psum crosses model-axis links.
    """
    B, L, d = x.shape
    act = _act(cfg.act)
    mo: MoEConfig = cfg.moe
    use_sm = (ctx.enabled and ctx.use_shard_map_moe
              and B % ctx.dp_size == 0
              and mo.d_ff_expert % ctx.tp_size == 0)
    if use_sm:
        bspec = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
        w_specs = {
            "ln": P(None),
            "router": P(None, None),
            "w_gate": P(None, None, ctx.model_axis),
            "w_up": P(None, None, ctx.model_axis),
            "w_down": P(None, ctx.model_axis, None),
        }
        if "shared" in p:
            w_specs["shared"] = {
                "w_gate": P(None, ctx.model_axis),
                "w_up": P(None, ctx.model_axis),
                "w_down": P(ctx.model_axis, None),
            }

        def body(xt, pp):
            xt2 = xt.reshape(-1, d)
            y, aux = _moe_local(pp, cfg, xt2, act, axis_name=ctx.model_axis)
            if "shared" in pp:
                sp = pp["shared"]
                zs = act(xt2 @ sp["w_gate"]) * (xt2 @ sp["w_up"])
                y = y + lax.psum(zs @ sp["w_down"], ctx.model_axis)
            aux = lax.pmean(aux, ctx.data_axes)
            return y.reshape(xt.shape), aux

        h = rms_norm(x, p["ln"], cfg.norm_eps)
        h = ctx.constraint(h, bspec, None, None)
        pp = {k: v for k, v in p.items() if k != "ln"}
        y, aux = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(bspec, None, None),
                      {k: w_specs[k] for k in pp}),
            out_specs=(P(bspec, None, None), P()),
            check_vma=False,
        )(h, pp)
        return y, aux
    # plain path (smoke tests, decode, tiny batches)
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    y, aux = _moe_local(p, cfg, h.reshape(-1, d), act)
    y = y.reshape(B, L, d)
    if "shared" in p:
        sp = p["shared"]
        zs = act(h @ sp["w_gate"]) * (h @ sp["w_up"])
        y = y + zs @ sp["w_down"]
    return y, aux
