import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


class ConstPredictor:
    """Shared constant output-length predictor for router/simulator/
    workflow tests (one definition; interface changes land here once)."""

    def __init__(self, v=150.0):
        self.v = float(v)

    def predict(self, prompts, input_lens, generated=None):
        import numpy as np
        return np.full(len(prompts), self.v, np.float32)


def arch_inputs(cfg, B=2, S=24, key=None):
    """Random tokens [B, S - n_prefix] and, for a configuration with
    prefix embeddings, the prefix [B, n_prefix, d_model] (else None)."""
    import jax
    key = jax.random.PRNGKey(0) if key is None else key
    n_pre = cfg.n_prefix_embeds
    toks = jax.random.randint(key, (B, S - n_pre), 0, cfg.vocab_size)
    pre = (jax.random.normal(key, (B, n_pre, cfg.d_model)) * 0.02
           if n_pre else None)
    return toks, pre
