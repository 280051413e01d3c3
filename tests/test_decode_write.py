"""How a decode step stores its new token in the KV cache.

The step writes only the new token's K/V rows into the cache, once per
row for all the layers of a stage.  The first test holds the row writes
to the same numbers as a whole-slice masked select, written out here;
the second checks that the step compiles to those row writes and to no
whole-layer write.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models import decode_step, init_cache, init_params
from repro.models import layers as L
from repro.models.model import logits_fn

MAX_LEN = 24          # full layers keep 24 slots, window layers 16
# rows: early, crossing the window ring's wrap (16), past the ring and
# near the full cache's end, and an idle slot fed token 0
POS0 = np.array([2, 13, 21, 7], np.int32)
IDLE = 3
N_STEPS = 5


def _config(arch):
    return reduce_config(get_config(arch), layers_per_stage=2)


def _reference_step(params, cfg, cache, tokens):
    """A decode step that selects the new token into each whole
    [B,S,KV,hd] layer slice and stores the slice back."""
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)
    stages = []
    for si, stage in enumerate(cfg.stages):
        sc = {k: dict(v) for k, v in cache["stages"][si].items()}
        for r in range(stage.repeat):
            for pi, blk in enumerate(stage.pattern):
                assert blk.mixer in ("full", "window") and blk.ffn == "dense"
                p_ = jax.tree.map(lambda a: a[r],
                                  params["stages"][si][f"blk{pi}"])
                c_ = sc[f"blk{pi}"]
                ck, cv = c_["k"][r], c_["v"][r]
                S = ck.shape[1]
                h = L.rms_norm(x, p_["attn"]["ln"], cfg.norm_eps)
                q, k, v = L._qkv(p_["attn"], cfg, h, pos[:, None])
                hit = (jnp.arange(S)[None, :]
                       == (pos % S)[:, None])[..., None, None]
                ck = jnp.where(hit, k, ck)
                cv = jnp.where(hit, v, cv)
                valid = ((jnp.arange(S)[None, :] <= pos[:, None])
                         | (pos[:, None] >= S))
                y = L._sdpa(q, ck, cv, valid[:, None, :], cfg.logit_softcap)
                x = x + y @ p_["attn"]["wo"]
                c_["k"] = c_["k"].at[r].set(ck)
                c_["v"] = c_["v"].at[r].set(cv)
                x = x + L.ffn_forward(p_["ffn"], cfg, x)
        stages.append(sc)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_fn(params, cfg, x[:, 0]), {"stages": stages,
                                             "pos": pos + 1}


def _random_cache(cfg, key):
    """A cache whose stale slots hold noise, so that a write to the
    wrong slot or a lost write shows."""
    cache = init_cache(cfg, len(POS0), MAX_LEN, dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(cache["stages"])
    keys = jax.random.split(key, len(leaves))
    leaves = [jax.random.normal(k, a.shape, a.dtype)
              for k, a in zip(keys, leaves)]
    return {"stages": jax.tree.unflatten(tree, leaves),
            "pos": jnp.asarray(POS0)}


@pytest.mark.parametrize("arch", ["minicpm-2b", "gemma3-12b"])
def test_row_write_matches_masked_select(arch):
    cfg = _config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ours = ref = _random_cache(cfg, jax.random.PRNGKey(1))
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t)[:2])
    ref_step = jax.jit(lambda p, c, t: _reference_step(p, cfg, c, t))
    toks = jax.random.randint(jax.random.PRNGKey(2), (N_STEPS, len(POS0), 1),
                              1, cfg.vocab_size)
    toks = toks.at[:, IDLE].set(0)
    for t in toks:
        lg, ours = step(params, ours, t)
        lg_ref, ref = ref_step(params, ref, t)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                                   rtol=1e-6, atol=1e-6)
        for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the window layers' ring wrapped, and the test saw every layer kind
    assert int(ours["pos"][1]) > 16
    assert {b.mixer for st in cfg.stages for b in st.pattern} == (
        {"full", "window"} if arch == "gemma3-12b" else {"full"})


def _cache_writes(cfg):
    """(update shape, written shape) of every dynamic_update_slice in the
    lowered decode step."""
    B = len(POS0)
    params = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=jnp.float32),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: init_cache(cfg, B, MAX_LEN, dtype=jnp.float32))
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    text = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t)) \
        .lower(params, cache, toks).as_text()
    dus = re.findall(r"stablehlo\.dynamic_update_slice .*?: \(tensor<([^>]*)>"
                     r", tensor<([^>]*)>", text)
    return [(u, t) for t, u in dus]


def test_decode_writes_rows_without_a_mesh_and_slices_with_one():
    cfg = _config("minicpm-2b")
    R = cfg.stages[0].repeat
    B, S, KV, hd = len(POS0), MAX_LEN, cfg.num_kv_heads, cfg.head_dim
    stack = f"{R}x{B}x{S}x{KV}x{hd}xf32"
    layer = f"1x{B}x{S}x{KV}x{hd}xf32"
    rows = f"{R}x1x1x{KV}x{hd}xf32"      # one row of every layer

    writes = _cache_writes(cfg)
    into_stack = [u for u, t in writes if t == stack]
    assert into_stack == [rows] * (2 * B), into_stack   # K and V, per row
    assert not [w for w in writes if w[0] == layer], writes
