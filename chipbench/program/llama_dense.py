"""The program's model configuration for a llama-style dense decoder,
built from the benchmark's configuration file: every size comes from the
file, and only the block pattern from the program's registry."""
from __future__ import annotations

import dataclasses

from repro.configs import get_config
from repro.configs.base import ModelConfig, Stage


def config(spec) -> ModelConfig:
    base = get_config(spec["program_arch"])
    n = spec["num_hidden_layers"]
    (stage,) = base.stages
    cfg = dataclasses.replace(
        base, num_layers=n, stages=(Stage(stage.pattern, n),),
        d_model=spec["hidden_size"], d_ff=spec["intermediate_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"], vocab_size=spec["vocab_size"],
        rope_theta=spec["rope_theta"], norm_eps=spec["rms_norm_eps"])
    # what the reference computes: tied logits, SiLU gate, no q/k norm,
    # no soft cap, full causal attention with a dense MLP in every layer
    got = (cfg.tie_embeddings, cfg.act, cfg.qk_norm, cfg.logit_softcap,
           [(b.mixer, b.ffn, b.window) for b in stage.pattern])
    if got != (True, "silu", False, None, [("full", "dense", None)]):
        raise ValueError(f"{spec['program_arch']} is not the dense decoder "
                         f"the reference computes: {got}")
    return cfg
