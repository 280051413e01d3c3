"""Dry-run cell construction: step functions + fully-sharded
ShapeDtypeStruct input specs for every (arch x shape x mesh) combination.

No device memory is ever allocated here: params/opt/cache shapes come from
``jax.eval_shape`` and inputs are ShapeDtypeStructs carrying
NamedShardings, so ``jax.jit(...).lower(**specs)`` is pure lowering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeSpec
from repro.distributed import sharding as shd
from repro.distributed.context import ShardCtx
from repro.models.model import decode_step, init_cache, init_params, prefill
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import make_grad_accum_step, make_train_step


def _pick_accum(cfg: ModelConfig) -> int:
    """Micro-batch count for train_4k: bounds per-step activation memory
    (global batch and per-optimizer-step FLOPs are unchanged — the accum
    loop is a scan inside the jitted step)."""
    n = cfg.param_count()
    if cfg.moe is not None or n > 20e9:
        return 4
    if n > 2e9:
        return 2
    return 1


def make_ctx(mesh) -> ShardCtx:
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    return ShardCtx(mesh=mesh, data_axes=data_axes, model_axis="model")


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _sds(shapes_tree, named_tree):
    return jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        shapes_tree, named_tree)


def _logits_spec(cfg: ModelConfig, mesh, batch: int, data_axes):
    b = shd._batch_entry(batch, mesh, data_axes)
    v = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    return P(b, v)


def build_cell(arch: str, shape_name: str, mesh, *,
               param_dtype_serve=jnp.bfloat16, ce_chunk: int = 512):
    """Returns (step_fn, kwargs_specs, out_shardings, donate_argnames,
    meta) for one dry-run cell."""
    cfg = get_config(arch)
    shape: ShapeSpec = SHAPES[shape_name]
    ctx = make_ctx(mesh)
    data_axes = ctx.data_axes
    B, S = shape.global_batch, shape.seq_len
    batch_entry = shd._batch_entry(B, mesh, data_axes)
    n_pre = cfg.n_prefix_embeds
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "batch": B, "seq": S, "mesh": dict(mesh.shape)}

    if shape.kind == "train":
        params_shapes = jax.eval_shape(
            lambda k: init_params(cfg, k, dtype=jnp.float32),
            jax.random.PRNGKey(0))
        opt_shapes = jax.eval_shape(init_opt_state, params_shapes)
        pspecs = shd.make_param_specs(params_shapes, mesh, fsdp=True)
        ospecs = shd.make_opt_specs(pspecs)
        p_named = _named(mesh, pspecs)
        o_named = _named(mesh, ospecs)
        accum = _pick_accum(cfg)
        micro = B // accum
        meta["grad_accum"] = accum
        mb_entry = shd._batch_entry(micro, mesh, data_axes)
        text_len = S - n_pre

        def tok_sds(L, dtype=jnp.int32):
            if accum == 1:
                return jax.ShapeDtypeStruct(
                    (B, L), dtype, sharding=NamedSharding(
                        mesh, P(mb_entry, None)))
            return jax.ShapeDtypeStruct(
                (accum, micro, L), dtype,
                sharding=NamedSharding(mesh, P(None, mb_entry, None)))

        kwargs = {
            "params": _sds(params_shapes, p_named),
            "opt_state": _sds(opt_shapes, o_named),
            "tokens": tok_sds(text_len),
            "labels": tok_sds(S),
            "mask": tok_sds(S, jnp.float32),
        }
        if n_pre:
            shp = ((B, n_pre, cfg.d_model) if accum == 1
                   else (accum, micro, n_pre, cfg.d_model))
            spec = (P(mb_entry, None, None) if accum == 1
                    else P(None, mb_entry, None, None))
            kwargs["prefix_embeds"] = jax.ShapeDtypeStruct(
                shp, jnp.bfloat16, sharding=NamedSharding(mesh, spec))
        opt_cfg = AdamWConfig(schedule=("wsd" if cfg.lr_schedule == "wsd"
                                        else "cosine"))
        if accum == 1:
            fn = make_train_step(cfg, opt_cfg, ctx, ce_chunk=ce_chunk)
        else:
            fn = make_grad_accum_step(cfg, opt_cfg, accum, ctx,
                                      ce_chunk=ce_chunk)
        out_shardings = (p_named, o_named, None)
        return fn, kwargs, out_shardings, ("params", "opt_state"), meta

    # serving paths: params in bf16, no optimizer
    params_shapes = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=param_dtype_serve),
        jax.random.PRNGKey(0))
    serve_fsdp = _needs_fsdp_serve(cfg, mesh)
    pspecs = shd.make_param_specs(params_shapes, mesh, fsdp=serve_fsdp)
    p_named = _named(mesh, pspecs)
    meta["serve_fsdp"] = serve_fsdp

    if shape.kind == "prefill":
        text_len = S - n_pre
        tok_sh = NamedSharding(mesh, P(batch_entry, None))
        kwargs = {
            "params": _sds(params_shapes, p_named),
            "tokens": jax.ShapeDtypeStruct((B, text_len), jnp.int32,
                                           sharding=tok_sh),
        }
        if n_pre:
            kwargs["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, n_pre, cfg.d_model), param_dtype_serve,
                sharding=NamedSharding(mesh, P(batch_entry, None, None)))
        cache_specs = shd.make_cache_specs(cfg, B, S, mesh,
                                           data_axes=data_axes)

        def prefill_step(params, tokens, prefix_embeds=None):
            return prefill(params, cfg, tokens, max_len=S,
                           prefix_embeds=prefix_embeds, ctx=ctx, remat=True)

        out_shardings = (NamedSharding(mesh, _logits_spec(cfg, mesh, B,
                                                          data_axes)),
                         _named(mesh, cache_specs))
        return prefill_step, kwargs, out_shardings, (), meta

    # decode
    cache_shapes = jax.eval_shape(
        functools.partial(init_cache, cfg, B, S, dtype=param_dtype_serve))
    cache_specs = shd.make_cache_specs(cfg, B, S, mesh, data_axes=data_axes)
    c_named = _named(mesh, cache_specs)
    kwargs = {
        "params": _sds(params_shapes, p_named),
        "cache": _sds(cache_shapes, c_named),
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                       sharding=NamedSharding(
                                           mesh, P(batch_entry, None))),
    }

    def serve_step(params, cache, tokens):
        return decode_step(params, cfg, cache, tokens, ctx=ctx)[:2]

    out_shardings = (NamedSharding(mesh, _logits_spec(cfg, mesh, B,
                                                      data_axes)), c_named)
    return serve_step, kwargs, out_shardings, ("cache",), meta


def _needs_fsdp_serve(cfg: ModelConfig, mesh, hbm_budget_gb: float = 6.0):
    """Whether serve params must be FSDP-sharded beyond TP to fit."""
    tp = int(mesh.shape["model"])
    bytes_per_chip = cfg.param_count() * 2 / tp
    return bytes_per_chip > hbm_budget_gb * 1e9


def input_specs(arch: str, shape_name: str, mesh):
    """The ShapeDtypeStruct stand-ins for every model input of a cell
    (public helper mirroring the harness's required interface)."""
    _, kwargs, _, _, _ = build_cell(arch, shape_name, mesh)
    return kwargs
