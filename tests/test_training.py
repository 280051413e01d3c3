"""Training substrate: schedules, AdamW, chunked CE, grad accumulation
equivalence, checkpoint round-trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models import init_params
from repro.training.checkpoint import (latest_step, restore_checkpoint,
                                       save_checkpoint)
from repro.training.loss import chunked_cross_entropy
from repro.training.optimizer import (AdamWConfig, adamw_update,
                                      init_opt_state, lr_at)
from repro.training.train_step import (make_grad_accum_step,
                                       make_train_step)

# JAX training loops: heavy compiles, opt-in via the full run
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def test_wsd_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      schedule="wsd", wsd_stable_frac=0.8, min_lr_frac=0.1)
    lrs = [float(lr_at(cfg, jnp.int32(s))) for s in range(100)]
    assert lrs[0] < lrs[9]                     # warmup rises
    assert lrs[20] == pytest.approx(1.0)       # stable plateau at peak
    assert lrs[70] == pytest.approx(1.0)
    assert lrs[99] < 0.2                       # sharp decay tail
    assert all(b <= a + 1e-6 for a, b in zip(lrs[10:], lrs[11:]))


def test_cosine_schedule_monotone_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=5, total_steps=50,
                      schedule="cosine")
    lrs = [float(lr_at(cfg, jnp.int32(s))) for s in range(5, 50)]
    assert all(b <= a + 1e-6 for a, b in zip(lrs, lrs[1:]))


def test_adamw_decreases_quadratic():
    p = {"w": jnp.array([3.0, -2.0])}
    opt = init_opt_state(p)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=200, schedule="const")
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, opt, _ = adamw_update(cfg, p, g, opt)
    assert float(jnp.sum(p["w"] ** 2)) < 1e-2


def test_chunked_ce_matches_direct():
    B, S, D, V = 2, 24, 16, 50
    h = jax.random.normal(KEY, (B, S, D))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (D, V))
    labels = jax.random.randint(KEY, (B, S), 0, V)
    mask = (jax.random.uniform(jax.random.fold_in(KEY, 2), (B, S))
            > 0.2).astype(jnp.float32)
    nll, ntok = chunked_cross_entropy(h, w, labels, mask, chunk=8)
    logits = (h @ w).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    direct = jnp.sum((lse - tgt) * mask) / jnp.sum(mask)
    np.testing.assert_allclose(float(nll), float(direct), rtol=1e-5)


def test_grad_accum_matches_full_batch():
    cfg = reduce_config(get_config("llama3.1-8b"))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    opt = init_opt_state(params)
    B, S, A = 4, 16, 2
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(KEY, 3), (B, S), 0,
                                cfg.vocab_size)
    mask = jnp.ones((B, S), jnp.float32)
    ocfg = AdamWConfig(lr=1e-3, total_steps=10, grad_clip=1e9)
    full = make_train_step(cfg, ocfg, ce_chunk=8)
    accum = make_grad_accum_step(cfg, ocfg, A, ce_chunk=8)
    p1, _, m1 = jax.jit(full)(params, opt, toks, labels, mask)
    p2, _, m2 = jax.jit(accum)(params, opt,
                               toks.reshape(A, B // A, S),
                               labels.reshape(A, B // A, S),
                               mask.reshape(A, B // A, S))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-5)


def test_checkpoint_roundtrip(tmp_path):
    cfg = reduce_config(get_config("llama3.1-8b"))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    opt = init_opt_state(params)
    save_checkpoint(tmp_path, 7, {"params": params, "opt": opt})
    assert latest_step(tmp_path) == 7
    restored = restore_checkpoint(tmp_path, 7)
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_resume_picks_latest(tmp_path):
    p = {"w": jnp.ones((3,))}
    for step in (1, 5, 3):
        save_checkpoint(tmp_path, step, {"params": p})
    assert latest_step(tmp_path) == 5
