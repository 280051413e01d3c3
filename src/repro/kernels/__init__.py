"""Pallas TPU kernels for the serving hot-spots (DESIGN.md §4):

- flash_attention: causal/windowed prefill attention (GQA)
- paged_attention: one-token decode over a paged KV cache
- ssd:             Mamba-2 chunked state-space scan

Each package ships <name>.py (pl.pallas_call + BlockSpec tiling),
ops.py (jit wrapper that compiles on TPU and interprets on CPU) and
ref.py (pure-jnp oracle used by the allclose test sweeps).
"""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend:
    compiled on TPU, interpreted on CPU, refused anywhere else so that
    nothing quietly interprets on an unknown device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on tpu (compiled) or cpu "
                       f"(interpreted), not on backend {backend!r}")
