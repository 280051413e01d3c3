"""The DeepSeek-V2 cell at a small size on the CPU: the plain reference
against the program's forward pass, the int8 control in the check's own
terms, the cost model against a count by hand, the new per-layer metric
on made-up runs, and whole rehearsals of the cell."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, harness, weights
from chipbench.cost import deepseek_v2 as cost
from chipbench.harness import Record, Run
from chipbench.program import deepseek_v2 as program
from chipbench.reference import deepseek_v2 as ref
from chipbench.tests import rehearsal
from chipbench.tests.test_harness import run

CELL = "deepseek-v2-lite.agent"
CONFIG = harness.ROOT / "chipbench" / "configs" / "deepseek-v2-lite.json"


def _spec(small=True):
    spec = json.loads(CONFIG.read_text())
    if small:
        spec.update(rehearsal.SMALL["deepseek-v2-lite"])
    return spec


@pytest.fixture(scope="module")
def model():
    spec = _spec()
    cfg = program.config(spec)
    from repro.models import init_params
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, dtype=jnp.float32),
                            jax.random.PRNGKey(0))
    params = weights.generate(ref.draw, spec, shapes, 2 ** 40 + 9,
                              jax.devices()[0])
    return spec, cfg, params


def test_reference_matches_the_program_forward(model):
    from repro.models import model_forward
    from repro.models.model import logits_fn
    spec, cfg, params = model
    V = spec["vocab_size"]
    toks = np.random.default_rng(0).integers(0, V, (2, 40)).astype(np.int32)
    want = np.asarray(ref.logits(params, spec, jnp.asarray(toks)))
    h, _ = model_forward(params, cfg, jnp.asarray(toks), remat=False)
    got = np.asarray(logits_fn(params, cfg, h))[..., :V]
    assert want.shape == (2, 40, V)
    # both float32: summation order only (bfloat16 products move a logit
    # by about 1e-2)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # a changed token changes the logits after it and not before
    toks2 = toks.copy()
    toks2[0, 20] = (toks2[0, 20] + 1) % V
    other = np.asarray(ref.logits(params, spec, jnp.asarray(toks2)))
    np.testing.assert_array_equal(other[0, :20], want[0, :20])
    assert np.abs(other[0, 20:] - want[0, 20:]).max() > 1e-2


def test_greedy_tokens_read_no_gap_and_the_int8_control_reads_one(model):
    spec, _, params = model
    rng = np.random.default_rng(1)
    fwd = jax.jit(lambda t: ref.logits(params, spec, t))
    seqs, starts = [], []
    for n in (24, 40, 56):
        toks = list(rng.integers(0, spec["vocab_size"], n))
        for _ in range(12):        # greedy continuation by the reference
            row = np.zeros((1, 80), np.int32)
            row[0, :len(toks)] = toks
            toks.append(int(jnp.argmax(fwd(row)[0, len(toks) - 1])))
        seqs.append(np.asarray(toks, np.int32))
        starts.append(n)
    served, ctrl = check.gaps(ref, spec, params, seqs, starts,
                              controls=("int8",))
    assert check.readings(served)["gap_mean"] == pytest.approx(0, abs=1e-5)
    assert check.readings(ctrl["int8"])["gap_mean"] > 1e-3


def test_the_int8_control_in_the_programs_place_is_not_correct(
        tmp_path_factory):
    # on the CPU the program multiplies in float32 as the reference does
    # and reads no gap; the limit lies between that and the control's
    root = rehearsal.make_root(tmp_path_factory.mktemp("tight"),
                               gap_mean=1e-5)
    cell = harness.load_cell(CELL, root)
    dev, seed = jax.devices()[0], 2 ** 33 + 21
    sv = harness.serve_phase(cell, seed, 3.0, False, [dev], 0.0,
                             log=lambda s: None)
    ok, table, ctrl = harness.decide(cell, sv.shapes, sv.rec, seed, dev,
                                     lambda s: None, ("int8",))
    assert ok is True, table
    assert ctrl["int8"][0] is False, ctrl["int8"][1]


def test_cost_counts_match_a_hand_count():
    spec = _spec(small=False)
    # one MoE layer's parts: attention 13,762,560 (wq 2048x3072, w_dkv
    # 2048x576, w_uk and w_uv 512x2048 each, wo 2048x2048), one expert
    # 3x2048x1408, the shared experts twice that, the gate 2048x64
    attn, expert, shared, gate = 13_762_560, 8_650_752, 17_301_504, 131_072
    dense, head = 3 * 2048 * 10944, 102400 * 2048
    norms = 2 * 2048 + 512
    # one request at context 1000: 6 of 64 picks, 8 held -> 0.75 of an
    # expert computed and 8 * 6/64 = 0.75 experts read in each MoE layer
    absorbed = (2048 * 3072 + 2048 * 576 + 16 * 128 * 512 * 2
                + 16 * 128 * 2048)
    flops = (2 * (14 * absorbed + dense + 13 * (gate + shared) + head)
             + 2 * 13 * 0.75 * expert + 14 * 2 * 16 * 1000 * (576 + 512))
    read = (14 * (attn + norms) + dense + 13 * (gate + shared
                                                + 0.75 * expert)
            + head + 2048 + 2048 + 14 * 576 * 1000)
    f, b = cost.decode(spec, [1000])
    assert f == pytest.approx(flops) and b == pytest.approx(2 * read)
    # a 256-token prompt: 256 * 6/64 * 8 = 192 expert MLPs per layer,
    # every held expert read (8 * (1 - (58/64)^256) = 8 to 1e-10), the
    # expanded attention's scores (128 + 64 wide) and values (128) over
    # 256^2 / 2 causal pairs
    flops = (2 * 256 * (14 * attn + dense + 13 * (gate + shared))
             + 2 * 13 * 192 * expert + 14 * 2 * 16 * 320 * 256 * 256 / 2
             + 2 * head)
    read = (14 * (attn + norms) + dense + 13 * (gate + shared + 8 * expert)
            + head + 2048 + 256 * 2048 + 14 * 576 * 256)
    f, b = cost.prefill(spec, 256)
    assert f == pytest.approx(flops) and b == pytest.approx(2 * read)
    # more requests read more experts, never more than are held
    b1, b32 = cost.decode(spec, [1000])[1], cost.decode(spec, [1000] * 32)[1]
    assert b1 < b32 < b1 + 2 * (13 * 8 * expert + 32 * 2048
                                + 14 * 576 * 31000)


class _Req:
    def __init__(self, prompt, generated, held):
        self.prompt_len, self.held_routes = prompt, held
        self.tokens = [0] * (prompt + generated)


class _Step:
    def __init__(self, req, counted=True):
        self.req, self.counted = req, counted


def _cell_run(steps):
    cell = harness.load_cell(CELL)
    rec = Record(steps=steps, routed=[1])
    return Run(cell, rec, 1.0, None, {}, None)


def test_held_route_share_reads_the_engines_counts():
    # 13 MoE layers x 6 picks = 78 per decode token; 10 + 2 decode tokens
    # (output less the prefill's first token), 130 of them held
    steps = [_Step(_Req(4, 11, 100)), _Step(_Req(9, 3, 30)),
             _Step(_Req(9, 1, 0)), _Step(_Req(9, 9, 500), counted=False)]
    share = harness.read_metric("held_route_share", _cell_run(steps))
    assert share == pytest.approx(100 * 130 / (12 * 78))
    assert harness.read_metric("held_route_share", _cell_run(steps[2:3])) \
        is None                                      # no decode token yet

    class _Plain:                   # an engine that counts no routes
        prompt_len, tokens = 2, [0] * 5
    assert harness.read_metric("held_route_share", _cell_run(
        [_Step(_Plain())])) is None


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("traced", [True, False])
def test_rehearsal_of_the_new_cell_is_correct(small_root, traced):
    res = run(small_root, CELL, traced=traced)
    assert res["correct"] is True and res["attempted"] > 0, res["check"]
    assert res["failed"] == 0
    assert res["metrics"] == {}                 # a CPU run reports none
    bench = json.loads((small_root / "BENCHMARK.json").read_text())
    for name in (w["name"] for w in bench["workloads"]):
        per_layer = {m["name"] for m in
                     harness.load_cell(name, small_root).per_layer}
        assert ("held_route_share" in per_layer) == (name == CELL), name
