"""Model operations and bytes of a llama-style dense decoder, from its
shapes: the least work a step needs, whatever implements it.

Operations count the matrix multiplications (2 per multiply-add) and the
attention over each request's actual context; bytes count every weight
once at the configuration's dtype and the key/value cache each request
actually reads or writes.  Norms, rotary embedding and softmax are left
out: they are a fraction of a percent of either.
"""
from __future__ import annotations

from typing import Sequence, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _sizes(spec):
    d, ff, V = (spec["hidden_size"], spec["intermediate_size"],
                spec["vocab_size"])
    qd = spec["num_attention_heads"] * spec["head_dim"]
    kvd = spec["num_key_value_heads"] * spec["head_dim"]
    L = spec["num_hidden_layers"]
    per_layer = d * qd + 2 * d * kvd + qd * d + 3 * d * ff
    weights = L * (per_layer + 2 * d) + V * d + d
    return d, qd, kvd, L, V, per_layer, weights


def decode(spec, contexts: Sequence[int]) -> Tuple[float, float]:
    """One decode step advancing requests whose contexts (positions
    attended, the new one included) are ``contexts``."""
    d, qd, kvd, L, V, per_layer, weights = _sizes(spec)
    nb = DTYPE_BYTES[spec["dtype"]]
    flops = sum(2 * (L * per_layer + V * d) + L * 4 * qd * c
                for c in contexts)
    cache = sum(L * 2 * kvd * c for c in contexts)
    return float(flops), float(nb * (weights + cache))


def prefill(spec, prompt_len: int) -> Tuple[float, float]:
    """One prompt of ``prompt_len`` tokens, causal, with logits for its
    last position only; the cache it writes is counted once."""
    d, qd, kvd, L, V, per_layer, weights = _sizes(spec)
    nb = DTYPE_BYTES[spec["dtype"]]
    p = prompt_len
    flops = 2 * L * per_layer * p + L * 2 * qd * p * p + 2 * V * d
    return float(flops), float(nb * (weights + L * 2 * kvd * p))
