"""Train step: mixed-precision loss + grads + AdamW update.

Params live in fp32 (master); the forward/backward runs in bf16 via a
cast at the top (cast is differentiable, so grads arrive back in fp32).
The MoE router stays fp32 for routing stability.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import lm_head_weight, model_forward
from repro.training.loss import chunked_cross_entropy
from repro.training.optimizer import AdamWConfig, adamw_update

_KEEP_F32 = {"router", "A_log", "D", "dt_bias"}


def cast_half(params, dtype=jnp.bfloat16):
    def cast(path, a):
        name = getattr(path[-1], "key", str(path[-1]))
        if name in _KEEP_F32 or a.ndim < 2 or a.dtype != jnp.float32:
            return a
        return a.astype(dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


def make_loss_fn(cfg: ModelConfig, ce_chunk: int = 512, remat: bool = True):
    def loss_fn(params, tokens, labels, mask, prefix_embeds=None):
        p_half = cast_half(params)
        hidden, aux = model_forward(p_half, cfg, tokens, prefix_embeds,
                                    remat=remat)
        w = lm_head_weight(p_half, cfg)
        nll, ntok = chunked_cross_entropy(hidden, w, labels, mask,
                                          chunk=ce_chunk)
        total = nll
        if cfg.moe is not None:
            total = total + cfg.moe.router_aux_weight * aux
        return total, {"nll": nll, "aux_loss": aux, "n_tokens": ntok}
    return loss_fn


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    ce_chunk: int = 512, remat: bool = True):
    loss_fn = make_loss_fn(cfg, ce_chunk, remat)

    def train_step(params, opt_state, tokens, labels, mask,
                   prefix_embeds=None):
        (total, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, labels, mask,
                                   prefix_embeds)
        new_params, new_opt, om = adamw_update(opt_cfg, params, grads,
                                               opt_state)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = total
        return new_params, new_opt, metrics

    return train_step


def make_grad_accum_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                         accum: int, ce_chunk: int = 512):
    """Micro-batched variant: batch leading dim is [accum, micro, ...]."""
    loss_fn = make_loss_fn(cfg, ce_chunk)

    def step(params, opt_state, tokens, labels, mask, prefix_embeds=None):
        def micro(carry, inp):
            g_acc, l_acc = carry
            args = (inp["tokens"], inp["labels"], inp["mask"],
                    inp.get("prefix_embeds"))
            (total, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, *args)
            g_acc = jax.tree.map(lambda a, b: a + b, g_acc, grads)
            return (g_acc, l_acc + total), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        batch = {"tokens": tokens, "labels": labels, "mask": mask}
        if prefix_embeds is not None:
            batch["prefix_embeds"] = prefix_embeds
        (grads, loss_sum), _ = jax.lax.scan(micro, (zeros, jnp.float32(0.0)),
                                            batch)
        grads = jax.tree.map(lambda g: g / accum, grads)
        new_params, new_opt, om = adamw_update(opt_cfg, params, grads,
                                               opt_state)
        metrics = {"loss": loss_sum / accum, **om}
        return new_params, new_opt, metrics

    return step
