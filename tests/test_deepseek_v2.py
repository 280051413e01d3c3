"""DeepSeek-V2 served through the engine at a small size on the CPU, on
seeded random weights: latent attention with YaRN, a dense first layer,
and MoE layers holding a share of the routed experts.

- prefill then decode through the latent cache agree with the plain
  float32 reference's full forward pass, logits to logits;
- the held parts of every share, with the shared experts counted once,
  add up to the uncut layer;
- no token is dropped: a batch in which every token picks the same held
  expert gives each token what it gets alone;
- the decode writes each new latent row in place, equal to a whole-slice
  masked select;
- ``held_routes`` counts the picks that fall on a held expert.
"""
import dataclasses
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.conftest import DEEPSEEK_SMALL as SMALL
from chipbench.program import deepseek_v2 as program
from chipbench.reference import deepseek_v2 as ref
from repro.engine.engine import EngineRequest, InferenceEngine
from repro.models import decode_step, init_cache, init_params
from repro.models import layers as L
from repro.models.model import logits_fn

CONFIG = pathlib.Path(weights.__file__).parent / "configs" / \
    "deepseek-v2-lite.json"
SEED = 2 ** 35 + 15
# float32 on both sides: the engine's absorbed decode and the reference's
# expanded attention sum in different orders, which moves a logit by
# about 1e-6 of its size; bfloat16 products move it by 1e-2 (checked
# below), so the tolerance tells the two apart
TOL = 1e-4


def _spec(**over):
    spec = json.loads(CONFIG.read_text())
    spec.update(SMALL, **over)
    return spec


def _model(spec, seed=SEED):
    cfg = program.config(spec)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, dtype=jnp.float32),
                            jax.random.PRNGKey(0))
    return cfg, weights.generate(ref.draw, spec, shapes, seed,
                                 jax.devices()[0])


def test_flat_rope_scaling_repeats_the_nested_group():
    spec = _spec()
    for k, v in spec["rope_scaling"].items():
        if k != "type":
            assert spec[f"rope_scaling_{k}"] == v, k


def test_engine_prefill_and_decode_match_the_reference_forward():
    spec = _spec()
    cfg, params = _model(spec)
    eng = InferenceEngine(cfg, params, max_batch=4, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [EngineRequest(rid=i, tokens=[int(t) for t in rng.integers(
        0, spec["vocab_size"], n)], prompt_len=n, max_new_tokens=8,
        keep_logits=True) for i, n in enumerate((9, 24, 40))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    fwd = jax.jit(lambda t, cdt: ref.logits(params, spec, t, cdt),
                  static_argnums=1)
    worst_bf16 = 0.0
    for r in reqs:
        toks = jnp.asarray(r.tokens, jnp.int32)[None]
        want = np.asarray(fwd(toks, jnp.float32))[0]
        got = np.stack(r.logits)[:, :spec["vocab_size"]]
        rows = want[r.prompt_len - 1:len(r.tokens) - 1]
        assert got.shape == rows.shape == (8, spec["vocab_size"])
        np.testing.assert_allclose(got, rows, atol=TOL, rtol=TOL)
        low = np.asarray(fwd(toks, jnp.bfloat16))[0]
        worst_bf16 = max(worst_bf16, float(np.abs(
            low[r.prompt_len - 1:len(r.tokens) - 1] - rows).max()))
    assert worst_bf16 > 10 * TOL, worst_bf16


def _moe_layer(spec, key):
    """An MoE layer's weights with every expert of the router, and x."""
    cfg = program.config(spec)
    full = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=None))
    p = L.init_moe(key, full, jnp.float32)
    p["ln"] = 0.1 * jax.random.normal(key, p["ln"].shape)
    return cfg, full, p


def _straight_layer(p, spec, x, held=None):
    """The layer written out per token: softmax over every router output,
    top-k weights not renormalised, the chosen experts among ``held``
    (default all) and the shared experts."""
    E = p["router"].shape[1]
    held = range(E) if held is None else held
    h = np.asarray(L.rms_norm(x, p["ln"], spec["rms_norm_eps"]), np.float64)
    h = h.reshape(-1, h.shape[-1])
    prec = jax.lax.Precision.HIGHEST
    logits = np.asarray(jnp.matmul(jnp.asarray(h, jnp.float32), p["router"],
                                   precision=prec), np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()
         if k.startswith("w_")}
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}

    def glu(t, g, u, d):
        a = t @ g
        return (a / (1 + np.exp(-a)) * (t @ u)) @ d

    y = glu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    for t in range(h.shape[0]):
        for e in np.argsort(-probs[t], kind="stable")[:spec[
                "num_experts_per_tok"]]:
            if e in held:
                y[t] += probs[t, e] * glu(h[t], w["w_gate"][e], w["w_up"][e],
                                          w["w_down"][e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("B,Lq", [(16, 1), (1, 16)])   # decode, prompt
def test_the_shares_add_up_to_the_uncut_layer(B, Lq):
    # 8 shares of 2 experts each: the router keeps all 16 outputs
    spec = _spec(n_routed_experts=2)
    cfg, full, p = _moe_layer(spec, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (B, Lq, cfg.d_model))
    h_ = cfg.moe.held
    hn, sp = L.rms_norm(x, p["ln"], cfg.norm_eps), p["shared"]
    shared = np.asarray((jax.nn.silu(hn @ sp["w_gate"]) * (hn @ sp["w_up"]))
                        @ sp["w_down"], np.float64)
    total = shared.copy()
    for s in range(cfg.moe.num_experts // h_):
        # this share holds experts [s*h, (s+1)*h): turned to ids [0, h)
        ids = slice(s * h_, (s + 1) * h_)
        ps = dict(p, router=jnp.roll(p["router"], -s * h_, axis=1),
                  w_gate=p["w_gate"][ids], w_up=p["w_up"][ids],
                  w_down=p["w_down"][ids])
        y, _, _ = L.moe_forward(ps, cfg, x)
        np.testing.assert_allclose(
            np.asarray(y), _straight_layer(p, spec, x, range(ids.start,
                                                              ids.stop)),
            atol=1e-5, rtol=1e-5)
        total += np.asarray(y, np.float64) - shared
    # float32 sums of 8 shares against float64: well under bfloat16's 4e-3
    want = _straight_layer(p, spec, x)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(L.moe_forward(p, full, x)[0]), want,
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,Lq", [(64, 1), (1, 64)])   # decode, prompt
def test_every_token_on_one_held_expert_drops_none(B, Lq):
    spec = _spec()
    cfg, _, p = _moe_layer(spec, jax.random.PRNGKey(5))
    p = dict(p, w_gate=p["w_gate"][:cfg.moe.held],
             w_up=p["w_up"][:cfg.moe.held], w_down=p["w_down"][:cfg.moe.held])
    # one router output far above the rest for every token: expert 1
    # (held) is every token's first pick
    h = L.rms_norm(jnp.ones((1, cfg.d_model)), p["ln"], spec["rms_norm_eps"])
    p["router"] = p["router"].at[:, 1].set(50.0 * h[0] / jnp.sum(h[0] ** 2))
    x = jnp.ones((B, Lq, cfg.d_model)) + 0.01 * jax.random.normal(
        jax.random.PRNGKey(6), (B, Lq, cfg.d_model))
    y, _, held = L.moe_forward(p, cfg, x)
    T = B * Lq
    alone = jnp.stack([L.moe_forward(p, cfg, x.reshape(T, 1, 1, -1)[t])[0]
                       for t in range(T)]).reshape(y.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(alone), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y), _straight_layer(
        p, spec, x, range(cfg.moe.held)), atol=1e-5, rtol=1e-5)
    assert int(held.min()) >= 1


MAX_LEN = 24
POS0 = np.array([2, 13, 21, 7], np.int32)


def _reference_step(params, cfg, cache, tokens):
    """A decode step that selects the new latent rows into each whole
    [B,S,...] layer slice and stores the slice back."""
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)
    stages = []
    for si, stage in enumerate(cfg.stages):
        (blk,) = stage.pattern
        sc = {k: dict(v) for k, v in cache["stages"][si].items()}
        c_ = sc["blk0"]
        for r in range(stage.repeat):
            p_ = jax.tree.map(lambda a: a[r], params["stages"][si]["blk0"])
            y, (cn, krn) = L.mla_decode(p_["attn"], cfg, x, c_["ckv"][r],
                                        c_["kr"][r], pos)
            c_["ckv"] = c_["ckv"].at[r].set(
                L.select_token(c_["ckv"][r], cn, pos))
            c_["kr"] = c_["kr"].at[r].set(L.select_token(c_["kr"][r], krn,
                                                         pos))
            x = x + y
            if blk.ffn == "dense":
                x = x + L.ffn_forward(p_["ffn"], cfg, x)
            else:
                x = x + L.moe_forward(p_["ffn"], cfg, x)[0]
        stages.append(sc)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_fn(params, cfg, x[:, 0]), {"stages": stages,
                                              "pos": pos + 1}


def test_mla_row_write_matches_masked_select():
    spec = _spec()
    cfg, params = _model(spec)
    B = len(POS0)
    cache = init_cache(cfg, B, MAX_LEN, dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(cache["stages"])
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    ours = ref_c = {"stages": jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape) for k, a in zip(keys, leaves)]),
        "pos": jnp.asarray(POS0)}
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t)[:2])
    ref_step = jax.jit(lambda p, c, t: _reference_step(p, cfg, c, t))
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, B, 1), 1,
                              cfg.vocab_size)
    for t in toks:
        lg, ours = step(params, ours, t)
        lg_ref, ref_c = ref_step(params, ref_c, t)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                                   rtol=1e-6, atol=1e-6)
        for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(ref_c)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the one cache buffer: with no mesh, the lowered step writes only
    # rows into the stacked latent caches, never a layer slice
    shapes = [jax.eval_shape(lambda: x) for x in (params, ours)]
    text = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t)).lower(
        *shapes, jax.ShapeDtypeStruct((B, 1), jnp.int32)).as_text()
    writes = re.findall(r"stablehlo\.dynamic_update_slice .*?: \(tensor<"
                        r"([^>]*)>, tensor<([^>]*)>", text)
    R = cfg.stages[1].repeat
    m = cfg.mla
    for width in (m.kv_lora_rank, m.rope_head_dim):
        stack = f"{R}x{B}x{MAX_LEN}x{width}xf32"
        got = [u for t, u in writes if t == stack]
        assert got == [f"{R}x1x1x{width}xf32"] * B, got


def test_held_routes_count_the_picks_on_held_experts():
    spec = _spec()
    cfg, params = _model(spec)
    # a router of zeros: every logit ties, and the top-6 are experts 0-5
    # (the lowest ids first), of which the 4 held ones count: per decode
    # token 4 picks in each of the 2 MoE layers
    params["stages"][1]["blk0"]["ffn"]["router"] = jnp.zeros_like(
        params["stages"][1]["blk0"]["ffn"]["router"])
    eng = InferenceEngine(cfg, params, max_batch=4, max_len=64)
    reqs = [EngineRequest(rid=i, tokens=[3] * n, prompt_len=n,
                          max_new_tokens=m)
            for i, (n, m) in enumerate(((5, 6), (12, 3), (7, 2)))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    # the first token comes from the prefill, which counts no routes
    assert [r.held_routes for r in reqs] == [(m - 1) * 2 * 4
                                             for m in (6, 3, 2)]
    # a model without MoE layers hands the host its tokens alone
    dense = dataclasses.replace(cfg, stages=cfg.stages[:1], num_layers=1)
    assert decode_step(init_params(dense, jax.random.PRNGKey(0),
                                   jnp.float32), dense,
                       init_cache(dense, 2, 8, jnp.float32),
                       jnp.zeros((2, 1), jnp.int32))[2] is None
