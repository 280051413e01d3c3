"""The weights a TPU engine serves from (``repro.models.serving_params``).

Every matmul weight is held in bfloat16, which is what a TPU's
default-precision dot rounds it to anyway; every other leaf is kept as it
was handed over.  On the CPU the engine keeps the tree it was handed, so
every CPU result is the float32 one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, reduce_config
from repro.engine.engine import DECODE, PREFILL, InferenceEngine
from repro.models import init_params, serving_params
from repro.models.model import MATMUL_WEIGHTS

# every other leaf name init_params makes: norm gains, the router, the
# SSD conv weights and decay terms, and the (gathered) embedding table
KEPT = {"ln", "gn", "q_norm", "k_norm", "kv_norm", "final_norm", "router",
        "conv_x", "conv_B", "conv_C", "conv_bx", "conv_bB", "conv_bC",
        "A_log", "D", "dt_bias", "embed"}
MAX_LEN = 32
# one of each mechanism the engine serves: full attention with tied
# logits, SSD, latent attention with held and shared experts and an
# untied head, sliding-window attention with experts and no shared ones
SERVED_ARCHS = ("minicpm-2b", "mamba2-1.3b", "deepseek-v2-lite-16b",
                "mixtral-8x22b")


def _params(arch):
    """Reduced widths, float32."""
    cfg = reduce_config(get_config(arch))
    params = jax.jit(lambda k: init_params(cfg, k, dtype=jnp.float32))(
        jax.random.PRNGKey(0))
    return cfg, params


def _shaped_like_params(arch):
    """Reduced widths, float32, each leaf drawn from N(0, 1): the tree
    of ``init_params`` without compiling it."""
    cfg = reduce_config(get_config(arch))
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    return cfg, jax.tree.map(
        lambda s: jnp.asarray(rng.standard_normal(s.shape, np.float32)),
        shapes)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_serving_params_casts_matmul_weights_and_keeps_the_rest(arch):
    cfg, params = _shaped_like_params(arch)
    served = serving_params(params)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    got, got_tree = jax.tree_util.tree_flatten_with_path(served)
    assert got_tree == tree
    for (path, a), (_, b) in zip(flat, got):
        name = path[-1].key
        assert name in MATMUL_WEIGHTS | KEPT, f"{name} is not classified"
        assert b.shape == a.shape, name
        if name in MATMUL_WEIGHTS:
            assert b.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(np.asarray(b),
                                          np.asarray(a.astype(jnp.bfloat16)))
        else:
            assert b is a, name
            assert b.dtype == jnp.float32, name
    if cfg.tie_embeddings:
        assert "lm_head" not in served


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_programs_take_served_weights(arch):
    """Prefill and one decode step run on the served tree (float32
    activations, bfloat16 matmul weights) and stay near the float32
    logits: rounding the weights moves a logit row by 0.2-1.1% here
    (relative L2), a wrong leaf or a dropped term by O(1)."""
    cfg, params = _params(arch)
    served = serving_params(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0,
                              cfg.vocab_size)
    nxt = jnp.array([[3]], jnp.int32)      # one next token for both
    rows = {}
    for name, p in (("f32", params), ("served", served)):
        logits, _, cache = PREFILL(p, toks, cfg=cfg, max_len=MAX_LEN)
        step, _, _ = DECODE(p, cache, nxt, cfg=cfg)
        rows[name] = np.concatenate([np.asarray(logits), np.asarray(step)])
    err = (np.linalg.norm(rows["served"] - rows["f32"], axis=-1)
           / np.linalg.norm(rows["f32"], axis=-1))
    assert np.all(err < 3e-2), err


def test_cpu_engine_holds_the_tree_it_was_handed():
    cfg, params = _params("deepseek-v2-lite-16b")
    eng = InferenceEngine(cfg, params, max_batch=2, max_len=MAX_LEN)
    assert eng.device.platform == "cpu"
    flat, tree = jax.tree_util.tree_flatten(params)
    held, held_tree = jax.tree_util.tree_flatten(eng.params)
    assert held_tree == tree
    for a, b in zip(flat, held):
        assert b.dtype == a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
    assert eng.weight_bytes == {"float32": sum(a.nbytes for a in flat)}
