"""Model operations and bytes of DeepSeek-V2 with one chip's share of the
experts, from its shapes: the least work a step needs, whatever
implements it.

Operations count the matrix multiplications (2 per multiply-add).  A
decode step counts the absorbed latent attention over each request's
actual context (the query absorbs the key up-projection, scores and
values are read from the latent, the value up-projection follows); a
prefill counts the expanded form over its p^2/2 causal pairs.  The gate,
the shared experts, the dense layer and the output head are counted in
full; of the held experts, a batch of B tokens routed uniformly over the
router's E outputs hits E_held * (1 - (1 - k/E)^B) of them on average,
each read once, and computes B * k * E_held / E expert MLPs.  Bytes count
each weight read once at the configuration's dtype (the embedding by the
rows gathered, not the table), and the latent cache each request
actually reads or writes.  Norms, rotary embedding, softmax and the
routing's sort are left out: they are a fraction of a percent of either.
"""
from __future__ import annotations

from typing import Sequence, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _sizes(spec):
    d, H, r = (spec["hidden_size"], spec["num_attention_heads"],
               spec["kv_lora_rank"])
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    L, k = spec["num_hidden_layers"], spec["first_k_dense_replace"]
    E, held = spec["published"]["n_routed_experts"], spec["n_routed_experts"]
    K, ff = spec["num_experts_per_tok"], spec["moe_intermediate_size"]
    attn = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    expert = 3 * d * ff
    shared = spec["n_shared_experts"] * expert
    dense = 3 * d * spec["intermediate_size"]
    return dict(d=d, H=H, r=r, dn=dn, dr=dr, dv=dv, L=L, k=k, E=E,
                held=held, K=K, attn=attn, expert=expert, shared=shared,
                dense=dense, router=d * E, V=spec["vocab_size"])


def _experts(s, B: int) -> Tuple[float, float]:
    """(expert MLPs computed, held experts read) a layer, for B tokens."""
    return (B * s["K"] * s["held"] / s["E"],
            s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** B))


def _weights(s, B: int) -> float:
    """Weights read by B tokens: everything but the experts not hit and
    the embedding rows not gathered."""
    moe = s["L"] - s["k"]
    per_norms = 2 * s["d"] + s["r"]
    return (s["L"] * (s["attn"] + per_norms) + s["k"] * s["dense"]
            + moe * (s["router"] + s["shared"]
                     + _experts(s, B)[1] * s["expert"])
            + s["V"] * s["d"] + s["d"] + B * s["d"])


def decode(spec, contexts: Sequence[int]) -> Tuple[float, float]:
    """One decode step advancing requests whose contexts (positions
    attended, the new one included) are ``contexts``."""
    s = _sizes(spec)
    nb = DTYPE_BYTES[spec["dtype"]]
    B, moe = len(contexts), s["L"] - s["k"]
    H, r, dn, dr, dv, d = s["H"], s["r"], s["dn"], s["dr"], s["dv"], s["d"]
    # absorbed: q W_uk^T and out_lat W_uv in place of the cache's up-projection
    attn_tok = (d * H * (dn + dr) + d * (r + dr) + H * dn * r + H * r * dv
                + H * dv * d)
    per_tok = (s["L"] * attn_tok + s["k"] * s["dense"]
               + moe * (s["router"] + s["shared"]) + s["V"] * d)
    flops = 2 * B * per_tok + 2 * moe * _experts(s, B)[0] * s["expert"]
    flops += sum(s["L"] * 2 * H * c * ((r + dr) + r) for c in contexts)
    cache = sum(s["L"] * (r + dr) * c for c in contexts)
    return float(flops), float(nb * (_weights(s, B) + cache))


def prefill(spec, prompt_len: int) -> Tuple[float, float]:
    """One prompt of ``prompt_len`` tokens, causal, expanded-form latent
    attention, with logits for its last position only; the latent cache
    it writes is counted once."""
    s = _sizes(spec)
    nb = DTYPE_BYTES[spec["dtype"]]
    p, moe = prompt_len, s["L"] - s["k"]
    pairs = p * p / 2
    flops = (2 * p * (s["L"] * s["attn"] + s["k"] * s["dense"]
                      + moe * (s["router"] + s["shared"]))
             + 2 * moe * _experts(s, p)[0] * s["expert"]
             + s["L"] * 2 * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * pairs
             + 2 * s["V"] * s["d"])
    cache = s["L"] * (s["r"] + s["dr"]) * p
    return float(flops), float(nb * (_weights(s, p) + cache))
