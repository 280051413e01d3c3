"""Model operations and bytes of a Mamba-2 decoder, from its shapes: the
least work a step needs, whatever implements it.

Operations count the projections (2 per multiply-add), the depthwise
convolution, and the SSD recurrence at its linear cost (state update and
read-out: 6 per state element per token); the chunked form that a
program may use for prefill does more, and that surplus is not counted.
Bytes count every weight once, and the recurrent and convolution state
that each request reads and writes, at the configuration's dtype.
"""
from __future__ import annotations

from typing import Sequence, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _sizes(spec):
    d, N, P, K = (spec["d_model"], spec["d_state"], spec["headdim"],
                  spec["d_conv"])
    di = spec["expand"] * d
    H, gn = di // P, spec["ngroups"] * N
    L, V = spec["n_layer"], spec["vocab_size"]
    proj = d * (2 * di + 2 * gn + H) + di * d
    per_tok = 2 * proj + 2 * K * (di + 2 * gn) + 6 * H * P * N
    layer_w = proj + K * (di + 2 * gn) + (di + 2 * gn) + 3 * H + 2 * d + di
    weights = L * layer_w + V * d + d
    state = H * P * N + (K - 1) * (di + 2 * gn)
    return L, V, d, per_tok, weights, state


def decode(spec, contexts: Sequence[int]) -> Tuple[float, float]:
    """One decode step for ``len(contexts)`` requests; the work per
    request does not depend on its context."""
    L, V, d, per_tok, weights, state = _sizes(spec)
    nb = DTYPE_BYTES[spec["dtype"]]
    n = len(contexts)
    flops = n * (L * per_tok + 2 * V * d)
    return float(flops), float(nb * (weights + n * L * 2 * state))


def prefill(spec, prompt_len: int) -> Tuple[float, float]:
    L, V, d, per_tok, weights, state = _sizes(spec)
    nb = DTYPE_BYTES[spec["dtype"]]
    flops = L * per_tok * prompt_len + 2 * V * d
    return float(flops), float(nb * (weights + L * state))
