"""Every arch's reduced config takes one jitted train step on CPU: the
loss and the gradient norm are finite and the parameters move.  Kept
apart from ``test_archs.py`` so that the two files run on different
workers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import arch_inputs
from repro.configs import ALL_ARCHS, get_config, reduce_config
from repro.models import init_params
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import make_train_step

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_runs_and_is_finite(arch):
    cfg = reduce_config(get_config(arch))
    params = init_params(cfg, KEY, dtype=jnp.float32)
    opt = init_opt_state(params)
    step = make_train_step(cfg, AdamWConfig(lr=1e-3, total_steps=10),
                           ce_chunk=8)
    B, S = 2, 16
    toks, pre = arch_inputs(cfg, B, S)
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
