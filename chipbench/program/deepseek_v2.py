"""The program's model configuration for DeepSeek-V2 (latent attention,
routed and shared experts), built from the benchmark's configuration
file: every size comes from the file, and only the block pattern from
the program's registry.  ``n_routed_experts`` is the experts this chip
holds; the router's width is ``published.n_routed_experts``."""
from __future__ import annotations

import dataclasses

from repro.configs import get_config
from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig, Stage,
                                YaRN)


def config(spec) -> ModelConfig:
    base = get_config(spec["program_arch"])
    n, k = spec["num_hidden_layers"], spec["first_k_dense_replace"]
    dense, moe = (st.pattern for st in base.stages)
    # YaRN's numbers from the flat rope_scaling_<key> copies, which the
    # reference reads too; the nested catalog group gives only its type
    ys = {k: spec[f"rope_scaling_{k}"] for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")}
    # what the reference computes: no q LoRA, YaRN rope, softmax gate with
    # greedy top-k in one group and no extra scaling, an MoE block in
    # every layer after the first k, untied head, SiLU experts, no
    # attention bias
    got = (spec["q_lora_rank"], spec["rope_scaling"]["type"],
           spec["scoring_func"],
           spec["topk_method"], spec["n_group"], spec["topk_group"],
           spec["routed_scaling_factor"], spec["moe_layer_freq"],
           spec["tie_word_embeddings"], spec["hidden_act"],
           spec["attention_bias"], [(b.mixer, b.ffn) for b in dense + moe],
           base.act, base.tie_embeddings)
    if got != (None, "yarn", "softmax", "greedy", 1, 1, 1, 1, False, "silu",
               False, [("mla", "dense"), ("mla", "moe")], "silu", False):
        raise ValueError(f"{spec['program_arch']} is not the DeepSeek-V2 "
                         f"decoder the reference computes: {got}")
    E = spec["published"]["n_routed_experts"]
    ff = spec["moe_intermediate_size"]
    return dataclasses.replace(
        base, num_layers=n, stages=(Stage(dense, k), Stage(moe, n - k)),
        d_model=spec["hidden_size"], d_ff=spec["intermediate_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"],
        vocab_size=spec["vocab_size"], rope_theta=spec["rope_theta"],
        norm_eps=spec["rms_norm_eps"],
        moe=MoEConfig(num_experts=E, top_k=spec["num_experts_per_tok"],
                      d_ff_expert=ff, num_shared=spec["n_shared_experts"],
                      d_ff_shared=spec["n_shared_experts"] * ff,
                      experts_held=spec["n_routed_experts"],
                      norm_topk_prob=spec["norm_topk_prob"]),
        mla=MLAConfig(kv_lora_rank=spec["kv_lora_rank"],
                      rope_head_dim=spec["qk_rope_head_dim"],
                      nope_head_dim=spec["qk_nope_head_dim"],
                      v_head_dim=spec["v_head_dim"],
                      yarn=YaRN(factor=ys["factor"],
                                original_max_position=ys[
                                    "original_max_position_embeddings"],
                                beta_fast=ys["beta_fast"],
                                beta_slow=ys["beta_slow"],
                                mscale=ys["mscale"],
                                mscale_all_dim=ys["mscale_all_dim"])))
