"""Model assembly: parameter init, train/prefill forward, one-token decode.

Layer stacks run as ``lax.scan`` over each stage's repeat axis (stage
pattern unrolled inside the body), so the lowered HLO is pattern-sized
rather than depth-sized, which keeps compiles of deep models short.
Caches are pytrees whose structure mirrors ``params["stages"]`` with a
leading repeat axis, letting decode scan over (params, cache) jointly.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import BlockSpec, ModelConfig, Stage
from repro.models import layers as L
from repro.models import ssd

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, blk: BlockSpec, dtype):
    kmix, kffn = jax.random.split(key)
    p = {}
    if blk.mixer in ("full", "window"):
        p["attn"] = L.init_attention(kmix, cfg, dtype)
    elif blk.mixer == "mla":
        p["attn"] = L.init_mla(kmix, cfg, dtype)
    elif blk.mixer == "mamba":
        p["mixer"] = ssd.init_mamba(kmix, cfg, dtype)
    if blk.ffn == "dense":
        d_ff = cfg.d_ff
        p["ffn"] = L.init_ffn(kffn, cfg, dtype, d_ff=d_ff)
    elif blk.ffn == "moe":
        p["ffn"] = L.init_moe(kffn, cfg, dtype)
    return p


def padded_vocab(cfg: ModelConfig, multiple: int = 256) -> int:
    """Pad the embedding table to a multiple of ``multiple`` rows, so the
    logits of odd released sizes like 151655 / 122753 fill whole TPU
    tiles.  Padded ids never appear in data; their logits join the
    softmax like any other never-sampled token (MaxText-style)."""
    return -(-cfg.vocab_size // multiple) * multiple


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    keys = jax.random.split(key, 4 + len(cfg.stages))
    V = padded_vocab(cfg)
    params = {
        "embed": (jax.random.normal(keys[0], (V, cfg.d_model),
                                    jnp.float32) * cfg.d_model ** -0.5
                  ).astype(dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[1], (cfg.d_model, V), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)
    if cfg.n_prefix_embeds:
        params["prefix_proj"] = (jax.random.normal(
            keys[2], (cfg.d_model, cfg.d_model), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)
    stages = []
    for si, stage in enumerate(cfg.stages):
        skey = jax.random.fold_in(keys[3], si)
        sp = {}
        for pi, blk in enumerate(stage.pattern):
            bkeys = jax.random.split(jax.random.fold_in(skey, pi), stage.repeat)
            sp[f"blk{pi}"] = jax.vmap(
                lambda k, blk=blk: _init_block(k, cfg, blk, dtype))(bkeys)
        stages.append(sp)
    params["stages"] = stages
    return params


# Leaves whose only use is as the weight operand of a default-precision
# dot.  Every other leaf is a norm gain, the router (multiplied at
# HIGHEST), an SSD conv weight or decay term, or the embedding table,
# which is gathered (and, tied, also the logits' weight).
MATMUL_WEIGHTS = frozenset({
    "wq", "wk", "wv", "wo",                       # attention
    "w_dkv", "w_uk", "w_uv",                      # MLA (and its wq, wo)
    "w_gate", "w_up", "w_down",                   # dense, shared, experts
    "w_z", "w_x", "w_B", "w_C", "w_dt", "out_proj",   # SSD
    "lm_head", "prefix_proj",
})


def serving_params(params):
    """The weights a TPU serves from: every ``MATMUL_WEIGHTS`` leaf in
    bfloat16, cast where it lies; every other leaf is the array handed
    over.  A TPU's default-precision dot rounds a float32 weight to
    bfloat16 at every call; held so, the weight reaches the matrix unit
    as the same bits, rounded once."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (a.astype(jnp.bfloat16)
                         if path[-1].key in MATMUL_WEIGHTS else a), params)


# ---------------------------------------------------------------------------
# Train / prefill blocks
# ---------------------------------------------------------------------------

def _apply_block(blk: BlockSpec, p, cfg: ModelConfig, x, positions,
                 collect_cache: bool, max_len: int):
    """One block, full sequence.  Returns (x, aux, cache_entry|None)."""
    aux = jnp.float32(0.0)
    entry = None
    if blk.mixer in ("full", "window"):
        y, (k, v) = L.attn_forward(p["attn"], cfg, x, positions, blk.window)
        x = x + y
        if collect_cache:
            S = min(blk.window, max_len) if blk.window else max_len
            k_c, v_c = _to_ring(k, S), _to_ring(v, S)
            entry = {"k": k_c, "v": v_c}
    elif blk.mixer == "mla":
        y, (ckv, kr) = L.mla_forward(p["attn"], cfg, x, positions)
        x = x + y
        if collect_cache:
            entry = {"ckv": _to_ring(ckv, max_len), "kr": _to_ring(kr, max_len)}
    elif blk.mixer == "mamba":
        if collect_cache:
            y, (conv_tail, state) = ssd.mamba_forward(
                p["mixer"], cfg, x, return_state=True)
            entry = {"conv": conv_tail, "ssm": state}
        else:
            y = ssd.mamba_forward(p["mixer"], cfg, x)
        x = x + y
    if blk.ffn == "dense":
        x = x + L.ffn_forward(p["ffn"], cfg, x)
    elif blk.ffn == "moe":
        y, a, _ = L.moe_forward(p["ffn"], cfg, x)
        x = x + y
        aux = aux + a
    return x, aux, entry


def _to_ring(k, S: int):
    """Place the last min(L, S) timesteps of k [B, L, ...] into a ring
    buffer of size S at slots (t % S), zero elsewhere."""
    B, Lt = k.shape[0], k.shape[1]
    take = min(Lt, S)
    tail = k[:, Lt - take:]
    slots = (jnp.arange(Lt - take, Lt)) % S
    buf = jnp.zeros((B, S) + k.shape[2:], k.dtype)
    return buf.at[:, slots].set(tail)


def _run_stages(params, cfg: ModelConfig, x, positions, remat: bool,
                collect_cache: bool, max_len: int):
    aux_total = jnp.float32(0.0)
    caches = []
    for si, stage in enumerate(cfg.stages):
        sp = params["stages"][si]
        multi = len(stage.pattern) > 1

        def body(carry, layer_p, stage=stage, multi=multi):
            xx, aux = carry
            entries = {}
            for pi, blk in enumerate(stage.pattern):
                apply = _apply_block
                if remat and multi:
                    # nested remat: the backward re-derives one block at a
                    # time, so a long pattern (jamba's 8, gemma3's 6)
                    # doesn't hold every block's attention/SSD temporaries
                    # live at once
                    apply = jax.checkpoint(
                        _apply_block,
                        static_argnums=(0, 2, 5, 6),  # blk/cfg/flags
                        prevent_cse=False)
                xx, a, entry = apply(
                    blk, layer_p[f"blk{pi}"], cfg, xx, positions,
                    collect_cache, max_len)
                aux = aux + a
                if entry is not None:
                    entries[f"blk{pi}"] = entry
            return (xx, aux), entries

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux_total), stage_cache = lax.scan(body, (x, aux_total), sp)
        caches.append(stage_cache)
    return x, aux_total, caches


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens, prefix_embeds):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.n_prefix_embeds and prefix_embeds is not None:
        pre = prefix_embeds.astype(x.dtype) @ params["prefix_proj"]
        x = jnp.concatenate([pre, x], axis=1)
    return x


def model_forward(params, cfg: ModelConfig, tokens, prefix_embeds=None,
                  remat: bool = True):
    """Teacher-forcing forward.  Returns (final_hidden [B,S,d], aux_loss)."""
    x = embed_tokens(params, cfg, tokens, prefix_embeds)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, aux, _ = _run_stages(params, cfg, x, positions, remat,
                            collect_cache=False, max_len=S)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def lm_head_weight(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def logits_fn(params, cfg: ModelConfig, hidden):
    w = lm_head_weight(params, cfg)
    return hidden @ w


def prefill(params, cfg: ModelConfig, tokens, max_len: int,
            prefix_embeds=None, remat: bool = False):
    """Process a prompt, build the KV/state cache sized ``max_len``.

    Returns (last_token_logits [B, V], cache).  ``cache["pos"]`` holds the
    per-request next position.
    """
    x = embed_tokens(params, cfg, tokens, prefix_embeds)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, _, caches = _run_stages(params, cfg, x, positions, remat,
                               collect_cache=True, max_len=max_len)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, x[:, -1])
    cache = {"stages": caches, "pos": jnp.full((B,), S, jnp.int32)}
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, ring: bool = True):
    """Empty cache: the engine's decode batch and staging buffers.

    ``ring=False`` sizes every attention buffer ``max_len`` with slot ==
    absolute position (no wrap): the staging layout ``prefill_chunk``
    writes into, converted to ring layout once via
    :func:`ring_convert_cache` when the finished prefill is spliced into
    a decode batch."""
    def blk_cache(blk: BlockSpec):
        if blk.mixer in ("full", "window"):
            S = (min(blk.window, max_len)
                 if (blk.window and ring) else max_len)
            shp = (batch, S, cfg.num_kv_heads, cfg.head_dim)
            return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
        if blk.mixer == "mla":
            m = cfg.mla
            return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                    "kr": jnp.zeros((batch, max_len, m.rope_head_dim), dtype)}
        if blk.mixer == "mamba":
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            gn = s.n_groups * s.d_state
            K1 = s.d_conv - 1
            return {"conv": {"x": jnp.zeros((batch, K1, di), dtype),
                             "B": jnp.zeros((batch, K1, gn), dtype),
                             "C": jnp.zeros((batch, K1, gn), dtype)},
                    "ssm": jnp.zeros((batch, s.n_heads(cfg.d_model),
                                      s.head_dim, s.d_state), jnp.float32)}
        return None

    stages = []
    for stage in cfg.stages:
        sc = {}
        for pi, blk in enumerate(stage.pattern):
            e = blk_cache(blk)
            if e is not None:
                sc[f"blk{pi}"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (stage.repeat,) + a.shape).copy(), e)
        stages.append(sc)
    return {"stages": stages, "pos": jnp.zeros((batch,), jnp.int32)}


def prefill_chunk(params, cfg: ModelConfig, cache, tokens, n_valid=None):
    """Extend a LINEAR cache (``init_cache(..., ring=False)``) by one
    prompt chunk — the engine's bounded-prefill-budget iteration, so a
    long prompt is admitted as several cheap steps interleaved with
    decode instead of one monolithic stall.

    tokens: [B, C] int32 (tail may be padding); ``n_valid``: [B] count
    of real tokens in the chunk (default: all C).  Padded positions
    write garbage K/V past the prompt; they are sliced off at ring
    conversion and masked (slot <= pos) until overwritten during decode,
    so they are never read.  Returns (logits at the last valid token
    [B, V], cache with ``pos`` advanced by ``n_valid``).

    Only full/window attention mixers are supported: mamba/MLA decode
    states are not chunk-resumable in this layout (the engine gates
    chunking off for those configs and falls back to one-shot prefill).
    """
    for blk in cfg.layer_list():
        if blk.mixer not in ("full", "window"):
            raise NotImplementedError(
                f"prefill_chunk supports full/window attention only, "
                f"got mixer {blk.mixer!r}")
    pos0 = cache["pos"]
    B, C = tokens.shape
    if n_valid is None:
        n_valid = jnp.full((B,), C, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0)

    new_stage_caches = []
    for si, stage in enumerate(cfg.stages):
        sp = params["stages"][si]
        sc = cache["stages"][si]

        # same carry-aliased scan as decode_step: the staging cache is
        # updated in place at the layer index, one buffer end to end
        def body(carry, inp, stage=stage):
            xx, cache_full = carry
            i, layer_p = inp
            layer_c = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                cache_full)
            new_c = {}
            for pi, blk in enumerate(stage.pattern):
                p_ = layer_p[f"blk{pi}"]
                c_ = layer_c[f"blk{pi}"]
                y, (ck, cv) = L.attn_chunk(
                    p_["attn"], cfg, xx, c_["k"], c_["v"], pos0,
                    blk.window)
                xx = xx + y
                new_c[f"blk{pi}"] = {"k": ck, "v": cv}
                if blk.ffn == "dense":
                    xx = xx + L.ffn_forward(p_["ffn"], cfg, xx)
                elif blk.ffn == "moe":
                    y2, _, _ = L.moe_forward(p_["ffn"], cfg, xx)
                    xx = xx + y2
            cache_full = jax.tree.map(
                lambda a, nc: lax.dynamic_update_index_in_dim(a, nc, i, 0),
                cache_full, new_c)
            return (xx, cache_full), None

        idx = jnp.arange(stage.repeat)
        (x, new_sc), _ = lax.scan(body, (x, sc), (idx, sp))
        new_stage_caches.append(new_sc)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[jnp.arange(B), jnp.clip(n_valid - 1, 0, C - 1)]
    logits = logits_fn(params, cfg, last)
    return logits, {"stages": new_stage_caches, "pos": pos0 + n_valid}


def ring_convert_cache(cfg: ModelConfig, cache, max_len: int, length: int):
    """Convert a finished linear staging cache (``prefill_chunk`` layout,
    slot == position, ``length`` valid rows) into the ring layout
    ``decode_step`` expects — identical to what ``prefill`` would have
    produced via ``_to_ring``.  Full-attention buffers embed unchanged;
    window buffers keep the last ``min(length, window)`` rows at slots
    ``t % S``."""
    stages = []
    for si, stage in enumerate(cfg.stages):
        sc = cache["stages"][si]
        new_sc = {}
        for pi, blk in enumerate(stage.pattern):
            key = f"blk{pi}"
            if key not in sc:
                continue
            e = sc[key]
            if blk.mixer in ("full", "window"):
                S = min(blk.window, max_len) if blk.window else max_len
                conv = jax.vmap(lambda a, S=S: _to_ring(a[:, :length], S))
                new_sc[key] = {"k": conv(e["k"]), "v": conv(e["v"])}
            else:
                new_sc[key] = e
        stages.append(new_sc)
    return {"stages": stages, "pos": cache["pos"]}


def decode_step(params, cfg: ModelConfig, cache, tokens):
    """One decode iteration.  tokens: [B, 1] int32.  Returns
    (logits [B, V], new_cache, held): ``held`` [B] int32 counts each
    slot's (MoE layer, top-k pick) pairs that fell on an expert this chip
    holds, None for a model without MoE layers."""
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)
    B = x.shape[0]
    held = (jnp.zeros((B,), jnp.int32)
            if any(b.ffn == "moe" for b in cfg.layer_list()) else None)

    new_stage_caches = []
    for si, stage in enumerate(cfg.stages):
        sp = params["stages"][si]
        sc = cache["stages"][si]

        # the cache rides the scan CARRY — XLA aliases while-loop carries,
        # so decode keeps a single cache buffer instead of stacked xs/ys
        # copies (which cost +2x cache per k/v at 32k contexts).  Attention
        # and MLA layers only read it and emit the token's rows, which
        # ``L.write_token_rows`` writes after the scan; an SSM layer's new
        # state is written at the layer index inside it.
        def body(carry, inp, stage=stage):
            xx, cache_full, held = carry
            i, layer_p = inp
            layer_c = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                cache_full)
            new_c, rows = {}, {}
            for pi, blk in enumerate(stage.pattern):
                p_ = layer_p[f"blk{pi}"]
                if blk.mixer in ("full", "window"):
                    c_ = layer_c[f"blk{pi}"]
                    y, (kn, vn) = L.attn_decode(
                        p_["attn"], cfg, xx, c_["k"], c_["v"], pos,
                        blk.window)
                    xx = xx + y
                    rows[f"blk{pi}"] = {"k": kn, "v": vn}
                elif blk.mixer == "mla":
                    c_ = layer_c[f"blk{pi}"]
                    y, (cn, krn) = L.mla_decode(
                        p_["attn"], cfg, xx, c_["ckv"], c_["kr"], pos)
                    xx = xx + y
                    rows[f"blk{pi}"] = {"ckv": cn, "kr": krn}
                elif blk.mixer == "mamba":
                    c_ = layer_c[f"blk{pi}"]
                    y, (conv_s, ssm_s) = ssd.mamba_decode(
                        p_["mixer"], cfg, xx, c_["conv"], c_["ssm"])
                    xx = xx + y
                    new_c[f"blk{pi}"] = {"conv": conv_s, "ssm": ssm_s}
                if blk.ffn == "dense":
                    xx = xx + L.ffn_forward(p_["ffn"], cfg, xx)
                elif blk.ffn == "moe":
                    y, _, h = L.moe_forward(p_["ffn"], cfg, xx)
                    xx = xx + y
                    held = held + h[:, 0]
            cache_full = {**cache_full, **{
                key: jax.tree.map(
                    lambda a, n: lax.dynamic_update_index_in_dim(a, n, i, 0),
                    cache_full[key], nc)
                for key, nc in new_c.items()}}
            return (xx, cache_full, held), rows

        idx = jnp.arange(stage.repeat)
        (x, new_sc, held), rows = lax.scan(body, (x, sc, held), (idx, sp))
        new_stage_caches.append({**new_sc, **{
            key: jax.tree.map(lambda a, n: L.write_token_rows(a, n, pos),
                              new_sc[key], r)
            for key, r in rows.items()}})

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, x[:, 0])
    new_cache = {"stages": new_stage_caches, "pos": pos + 1}
    return logits, new_cache, held
