"""The plain float32 references against the program's own forward pass
on the CPU at a small size, with weights made by the benchmark; and the
control (int8 weights, bfloat16 activations) in the check's own terms."""
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, harness, weights
from chipbench.tests.rehearsal import SMALL


def _setup(name):
    spec = json.loads((harness.ROOT / "chipbench" / "configs"
                       / f"{name}.json").read_text())
    spec.update(SMALL[name])
    ref = importlib.import_module(f"chipbench.reference.{spec['arch']}")
    cfg = importlib.import_module(
        f"chipbench.program.{spec['arch']}").config(spec)
    from repro.models import init_params
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, dtype=jnp.float32),
                            jax.random.PRNGKey(0))
    params = weights.generate(ref.draw, spec, shapes, 2 ** 40 + 3,
                              jax.devices()[0])
    return spec, ref, cfg, params


@pytest.mark.parametrize("name", ["minicpm-2b", "mamba2-1.3b"])
def test_reference_matches_the_program_forward(name):
    from repro.models import model_forward
    from repro.models.model import logits_fn
    spec, ref, cfg, params = _setup(name)
    toks = np.random.default_rng(0).integers(0, spec["vocab_size"],
                                             (2, 40)).astype(np.int32)
    want = np.asarray(ref.logits(params, spec, jnp.asarray(toks)))
    h, _ = model_forward(params, cfg, jnp.asarray(toks), remat=False)
    got = np.asarray(logits_fn(params, cfg, h))[..., :spec["vocab_size"]]
    assert want.shape == (2, 40, spec["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # a changed token changes the logits after it and not before
    toks2 = toks.copy()
    toks2[0, 20] = (toks2[0, 20] + 1) % spec["vocab_size"]
    other = np.asarray(ref.logits(params, spec, jnp.asarray(toks2)))
    np.testing.assert_array_equal(other[0, :20], want[0, :20])
    assert np.abs(other[0, 20:] - want[0, 20:]).max() > 1e-2


def test_weights_are_made_from_the_seed():
    spec, ref, cfg, params = _setup("minicpm-2b")
    again = _setup("minicpm-2b")[3]
    other = weights.generate(ref.draw, spec, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params), 7,
        jax.devices()[0])
    a, b, c = (jax.tree.leaves(p) for p in (params, again, other))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    emb = np.asarray(params["embed"])
    assert emb.shape[0] > spec["vocab_size"]
    assert not emb[spec["vocab_size"]:].any()        # padded rows stay 0
    assert np.abs(weights.key_words(2 ** 33 + 5) - [5, 2]).max() == 0


@pytest.mark.parametrize("name", ["minicpm-2b", "mamba2-1.3b"])
def test_greedy_tokens_read_no_gap_and_the_control_reads_one(name):
    spec, ref, cfg, params = _setup(name)
    rng = np.random.default_rng(1)
    seqs, starts = [], []
    fwd = jax.jit(lambda t: ref.logits(params, spec, t))
    for n in (24, 40, 56):
        toks = list(rng.integers(0, spec["vocab_size"], n))
        for _ in range(12):        # greedy continuation by the reference
            row = np.zeros((1, 80), np.int32)
            row[0, :len(toks)] = toks
            toks.append(int(jnp.argmax(fwd(row)[0, len(toks) - 1])))
        seqs.append(np.asarray(toks, np.int32))
        starts.append(n)
    served, ctrl = check.gaps(ref, spec, params, seqs, starts,
                              controls=("int8", "bf16"))
    assert [len(g) for g in served] == [12, 12, 12]
    assert check.readings(served)["gap_mean"] == pytest.approx(0, abs=1e-5)
    assert check.readings(ctrl["int8"])["gap_mean"] > 0
    assert [len(g) for g in ctrl["bf16"]] == [12, 12, 12]
    # a token altered where it is produced reads a gap
    bad = [s.copy() for s in seqs]
    bad[0][-3] = (bad[0][-3] + 1) % spec["vocab_size"]
    served, _ = check.gaps(ref, spec, params, bad, starts)
    assert check.readings(served)["gap_max"] > 0.1


def test_int8_weights_round_matrices_per_output_channel():
    w = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 4)),
                    jnp.float32)
    p = {"stages": [{"blk0": {"ffn": {"w_up": w, "ln": w[:, 0]}}}],
         "embed": w[0]}
    q = check.int8_weights(p)
    wq = np.asarray(q["stages"][0]["blk0"]["ffn"]["w_up"], np.float32)
    scale = np.abs(np.asarray(w)).max(axis=1, keepdims=True) / 127
    assert np.abs(wq - np.asarray(w)).max() <= scale.max() * 0.51
    assert q["stages"][0]["blk0"]["ffn"]["ln"] is p["stages"][0]["blk0"][
        "ffn"]["ln"]
    assert q["embed"].dtype == jnp.bfloat16
