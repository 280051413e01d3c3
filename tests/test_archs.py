"""Per-architecture smoke tests: every arch's reduced config runs
forward + prefill/decode on CPU with correct shapes and finite outputs;
decode must agree with teacher forcing.  (The train step of every arch
is in ``test_train_step_archs.py``, so that the two run on different
workers.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import arch_inputs
from repro.configs import ALL_ARCHS, get_config, reduce_config
from repro.models import decode_step, init_params, model_forward, prefill
from repro.models.model import logits_fn, padded_vocab

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_decode_consistency(arch):
    cfg = reduce_config(get_config(arch))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    B, S = 2, 24
    toks, pre = arch_inputs(cfg, B, S)
    h, aux = model_forward(params, cfg, toks, pre, remat=False)
    assert h.shape == (B, S, cfg.d_model)
    logits_tf = logits_fn(params, cfg, h)
    assert logits_tf.shape[-1] == padded_vocab(cfg)
    assert np.isfinite(np.asarray(logits_tf)).all()

    lg_pf, cache = prefill(params, cfg, toks[:, :-1], max_len=S + 4,
                           prefix_embeds=pre)
    np.testing.assert_allclose(np.asarray(lg_pf),
                               np.asarray(logits_tf[:, -2]),
                               rtol=2e-3, atol=2e-3)
    lg_dec, cache, _ = decode_step(params, cfg, cache, toks[:, -1:])
    np.testing.assert_allclose(np.asarray(lg_dec),
                               np.asarray(logits_tf[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_multi_token_decode_matches_teacher_forcing():
    cfg = reduce_config(get_config("jamba-v0.1-52b"))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    B, S, n_dec = 2, 20, 4
    toks, _ = arch_inputs(cfg, B, S)
    h, _ = model_forward(params, cfg, toks, remat=False)
    logits_tf = logits_fn(params, cfg, h)
    _, cache = prefill(params, cfg, toks[:, :S - n_dec], max_len=S + 2)
    for i in range(S - n_dec, S):
        lg, cache, _ = decode_step(params, cfg, cache, toks[:, i:i + 1])
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits_tf[:, i]),
                                   rtol=3e-3, atol=3e-3)


def test_param_counts_match_published():
    expected = {
        "gemma3-27b": 27.0e9, "gemma3-12b": 11.8e9, "qwen3-32b": 32.8e9,
        "jamba-v0.1-52b": 51.5e9, "mixtral-8x22b": 140.6e9,
        "deepseek-v2-lite-16b": 15.7e9, "llama3.1-8b": 8.0e9,
        "mamba2-1.3b": 1.3e9,
    }
    for arch, n in expected.items():
        got = get_config(arch).param_count()
        assert abs(got - n) / n < 0.05, (arch, got)


def test_moe_active_params():
    cfg = get_config("mixtral-8x22b")
    active = cfg.param_count(active_only=True)
    assert 35e9 < active < 44e9   # published ~39B active


def test_forward_takes_lengths_off_the_attention_chunk():
    """Sequences longer than the 512-query attention chunk need not be a
    multiple of it: causal outputs on a prefix match the prefix alone."""
    cfg = reduce_config(get_config("minicpm-2b"))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 600), 0,
                              cfg.vocab_size)
    fwd = jax.jit(lambda p, t: model_forward(p, cfg, t, remat=False)[0])
    long, short = fwd(params, toks), fwd(params, toks[:, :500])
    np.testing.assert_allclose(np.asarray(long[:, :500]),
                               np.asarray(short), rtol=1e-5, atol=1e-5)
