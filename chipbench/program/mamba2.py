"""The program's model configuration for a Mamba-2 decoder, built from
the benchmark's configuration file: every size comes from the file, and
only the block pattern from the program's registry."""
from __future__ import annotations

import dataclasses

from repro.configs import get_config
from repro.configs.base import ModelConfig, SSMConfig, Stage


def config(spec) -> ModelConfig:
    base = get_config(spec["program_arch"])
    n = spec["n_layer"]
    (stage,) = base.stages
    cfg = dataclasses.replace(
        base, num_layers=n, stages=(Stage(stage.pattern, n),),
        d_model=spec["d_model"], vocab_size=spec["vocab_size"],
        norm_eps=spec["norm_epsilon"],
        ssm=SSMConfig(d_state=spec["d_state"], d_conv=spec["d_conv"],
                      expand=spec["expand"], head_dim=spec["headdim"],
                      n_groups=spec["ngroups"], chunk=spec["chunk_size"]))
    got = (cfg.tie_embeddings, [(b.mixer, b.ffn) for b in stage.pattern])
    if got != (True, [("mamba", "none")]) or spec["ngroups"] != 1:
        raise ValueError(f"{spec['program_arch']} is not the Mamba-2 "
                         f"decoder the reference computes: {got}")
    return cfg
