"""Model/config system for the repro framework.

A ``ModelConfig`` fully describes a decoder-only LM backbone (dense, MoE,
SSM, or hybrid) plus optional modality-stub frontends.  Layer stacks are
expressed as *stages*: a stage is a repeating pattern of blocks that the
model applies with ``jax.lax.scan`` over the repeat axis, keeping the HLO
compact (pattern-sized, not depth-sized).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Block / stage specs
# ---------------------------------------------------------------------------

# mixer kinds: "full" (GQA, full causal), "window" (GQA, sliding window),
#              "mla" (DeepSeek multi-head latent attention), "mamba" (SSD)
# ffn kinds:   "dense" (gated MLP), "moe" (routed experts), "none"


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    mixer: str            # full | window | mla | mamba
    ffn: str              # dense | moe | none
    window: Optional[int] = None  # sliding-window length for mixer=="window"

    def __post_init__(self):
        assert self.mixer in ("full", "window", "mla", "mamba"), self.mixer
        assert self.ffn in ("dense", "moe", "none"), self.ffn
        if self.mixer == "window":
            assert self.window is not None and self.window > 0


@dataclasses.dataclass(frozen=True)
class Stage:
    pattern: Tuple[BlockSpec, ...]
    repeat: int

    @property
    def num_layers(self) -> int:
        return len(self.pattern) * self.repeat


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared: int = 0           # always-on shared experts (DeepSeek style)
    d_ff_shared: int = 0          # hidden dim of the fused shared expert
    router_aux_weight: float = 0.01
    # the experts this chip holds, ids [0, experts_held), under expert
    # parallelism (None: all of them); the router keeps all num_experts
    experts_held: Optional[int] = None
    norm_topk_prob: bool = True   # renormalise the top-k routing weights

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling as DeepSeek-V2 defines it (arXiv:2309.00071)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128
    yarn: Optional[YaRN] = None   # rope scaling of q_pe / k_pe


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | hybrid | ssm | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    stages: Tuple[Stage, ...]
    # attention details
    qk_norm: bool = False
    rope_theta: float = 10000.0
    logit_softcap: Optional[float] = None
    # sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # frontends ("none" | "vision_stub" | "audio_stub")
    frontend: str = "none"
    n_prefix_embeds: int = 0      # stub modality embeddings prepended to text
    # misc
    tie_embeddings: bool = False
    act: str = "silu"             # silu | gelu
    norm_eps: float = 1e-6
    # training
    lr_schedule: str = "cosine"   # cosine | wsd
    # citation provenance
    source: str = ""

    def __post_init__(self):
        got = sum(s.num_layers for s in self.stages)
        assert got == self.num_layers, (
            f"{self.name}: stages cover {got} layers, config says {self.num_layers}")

    # -- derived quantities -------------------------------------------------

    @property
    def attn_q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def attn_kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def block_kinds(self):
        """Iterate (stage_idx, pattern_idx, BlockSpec) over unique block slots."""
        for si, st in enumerate(self.stages):
            for pi, blk in enumerate(st.pattern):
                yield si, pi, blk

    def layer_list(self):
        """Flat list of BlockSpec, one per actual layer."""
        out = []
        for st in self.stages:
            for _ in range(st.repeat):
                out.extend(st.pattern)
        return out

    # -- parameter counting (analytic; the simulator's model footprint) ----

    def param_count(self, *, active_only: bool = False) -> int:
        d = self.d_model
        n = 0
        # embeddings (+ untied lm head)
        n += self.vocab_size * d
        if not self.tie_embeddings:
            n += self.vocab_size * d
        if self.n_prefix_embeds:
            n += d * d  # frontend projection stub
        for blk in self.layer_list():
            n += d  # input norm
            if blk.mixer in ("full", "window"):
                n += d * self.attn_q_dim + 2 * d * self.attn_kv_dim
                n += self.attn_q_dim * d
                if self.qk_norm:
                    n += 2 * self.head_dim
            elif blk.mixer == "mla":
                m = self.mla
                n += d * self.num_heads * (m.nope_head_dim + m.rope_head_dim)  # wq
                n += d * (m.kv_lora_rank + m.rope_head_dim)                    # w_dkv
                n += m.kv_lora_rank                                            # kv norm
                n += m.kv_lora_rank * self.num_heads * m.nope_head_dim         # w_uk
                n += m.kv_lora_rank * self.num_heads * m.v_head_dim            # w_uv
                n += self.num_heads * m.v_head_dim * d                         # wo
            elif blk.mixer == "mamba":
                s = self.ssm
                di = s.d_inner(d)
                nh = s.n_heads(d)
                conv_ch = di + 2 * s.n_groups * s.d_state
                n += d * (2 * di + 2 * s.n_groups * s.d_state + nh)  # in_proj
                n += s.d_conv * conv_ch                               # conv
                n += 3 * nh                                           # A_log, D, dt_bias
                n += di                                               # gated norm
                n += di * d                                           # out_proj
            if blk.ffn == "dense":
                n += d  # pre-ffn norm
                n += 3 * d * self.d_ff
            elif blk.ffn == "moe":
                mo = self.moe
                n += d
                n += d * mo.num_experts  # router
                e = mo.held if not active_only else mo.top_k
                n += 3 * d * mo.d_ff_expert * e
                if mo.num_shared:
                    n += 3 * d * mo.d_ff_shared
        return n


# ---------------------------------------------------------------------------
# Stage builders
# ---------------------------------------------------------------------------

def uniform_stage(num_layers: int, mixer: str = "full", ffn: str = "dense",
                  window: Optional[int] = None) -> Tuple[Stage, ...]:
    return (Stage(pattern=(BlockSpec(mixer, ffn, window),), repeat=num_layers),)


def local_global_stages(num_layers: int, local_per_global: int,
                        window: int, ffn: str = "dense") -> Tuple[Stage, ...]:
    """Gemma-3 style N:1 local:global interleave; trailing locals get their
    own stage when num_layers isn't a multiple of the pattern length."""
    plen = local_per_global + 1
    pat = tuple(BlockSpec("window", ffn, window) for _ in range(local_per_global)) \
        + (BlockSpec("full", ffn),)
    reps, rem = divmod(num_layers, plen)
    stages = [Stage(pattern=pat, repeat=reps)]
    if rem:
        tail = tuple(BlockSpec("window", ffn, window) for _ in range(rem))
        stages.append(Stage(pattern=tail, repeat=1))
    return tuple(stages)


def reduce_config(cfg: ModelConfig, *, layers_per_stage: int = 1,
                  d_model: int = 64, d_ff: int = 128, vocab: int = 256,
                  num_experts: Optional[int] = None) -> ModelConfig:
    """Shrink a config to smoke-test size while preserving its block mix."""
    heads = max(2, min(4, cfg.num_heads))
    kv = 1 if cfg.num_kv_heads < cfg.num_heads else heads
    if cfg.num_kv_heads == cfg.num_heads:
        kv = heads
    head_dim = d_model // heads
    stages = []
    for st in cfg.stages:
        pat = []
        for b in st.pattern:
            w = min(b.window, 16) if b.window else None
            pat.append(BlockSpec(b.mixer, b.ffn, w))
        stages.append(Stage(tuple(pat), min(st.repeat, layers_per_stage)))
    stages = tuple(stages)
    nl = sum(s.num_layers for s in stages)
    moe = None
    if cfg.moe is not None:
        ne = num_experts or min(cfg.moe.num_experts, 4)
        moe = MoEConfig(num_experts=ne, top_k=min(cfg.moe.top_k, 2),
                        d_ff_expert=d_ff // 2,
                        num_shared=min(cfg.moe.num_shared, 1),
                        d_ff_shared=d_ff // 2 if cfg.moe.num_shared else 0,
                        norm_topk_prob=cfg.moe.norm_topk_prob)
    mla = None
    if cfg.mla is not None:
        mla = MLAConfig(kv_lora_rank=32, rope_head_dim=16, nope_head_dim=head_dim,
                        v_head_dim=head_dim, yarn=cfg.mla.yarn)
    ssm = None
    if cfg.ssm is not None:
        ssm = SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16,
                        n_groups=1, chunk=16)
    return dataclasses.replace(
        cfg, name=cfg.name + "-reduced", num_layers=nl, d_model=d_model,
        num_heads=heads, num_kv_heads=kv, head_dim=head_dim, d_ff=d_ff,
        vocab_size=vocab, stages=stages, moe=moe, mla=mla, ssm=ssm,
        n_prefix_embeds=min(cfg.n_prefix_embeds, 4))
