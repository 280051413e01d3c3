"""Per-architecture smoke tests (assignment deliverable f): every arch's
reduced config runs forward + train-step + prefill/decode on CPU with
correct shapes and finite outputs; decode must agree with teacher forcing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, reduce_config
from repro.distributed.context import NULL_CTX
from repro.models import (decode_step, init_cache, init_params,
                          model_forward, prefill)
from repro.models.model import logits_fn, padded_vocab
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import make_train_step

KEY = jax.random.PRNGKey(0)

# Full forward/train/decode over every arch dominates the tier-1 wall
# clock; the param-count checks below stay in the default run.
slow = pytest.mark.slow


def _inputs(cfg, B=2, S=24):
    n_pre = cfg.n_prefix_embeds
    toks = jax.random.randint(KEY, (B, S - n_pre), 0, cfg.vocab_size)
    pre = (jax.random.normal(KEY, (B, n_pre, cfg.d_model)) * 0.02
           if n_pre else None)
    return toks, pre


@slow
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_decode_consistency(arch):
    cfg = reduce_config(get_config(arch))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    B, S = 2, 24
    toks, pre = _inputs(cfg, B, S)
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


@slow
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_runs_and_is_finite(arch):
    cfg = reduce_config(get_config(arch))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    opt = init_opt_state(params)
    step = make_train_step(cfg, AdamWConfig(lr=1e-3, total_steps=10),
                           NULL_CTX, ce_chunk=8)
    B, S = 2, 16
    toks, pre = _inputs(cfg, B, S)
    labels = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    mask = jnp.ones((B, S), jnp.float32)
    new_p, new_o, metrics = jax.jit(step)(params, opt, toks, labels, mask,
                                          pre)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_o["step"]) == 1
    # params actually moved
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(new_p),
                                jax.tree.leaves(params)))
    assert delta > 0


@slow
def test_multi_token_decode_matches_teacher_forcing():
    cfg = reduce_config(get_config("jamba-v0.1-52b"))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    B, S, n_dec = 2, 20, 4
    toks, _ = _inputs(cfg, B, S)
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
