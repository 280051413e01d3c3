"""The check that decides ``correct``, shown to fail: a whole run on the
CPU at a small size with the timed path broken underneath (a token
altered where it is produced, a decode step that returns its state
unchanged, half of the batch's inputs left out, one replica of four
altered) reads ``correct`` false, and so does the control put in the
program's place under the same limits.  Replicas exchange nothing, so
there is no exchange between chips to leave out."""
import functools
import time

import jax
import pytest

from chipbench import harness
from chipbench.tests import rehearsal
from chipbench.tests.test_harness import CELLS, run


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


def _wrap_decode(engines, change):
    for e in engines:
        e._decode = functools.partial(change, e._decode, e)


def _alter_token(engines):
    def change(f, e, p, c, t):
        lg, nxt, c = f(p, c, t)
        return lg, (nxt + 1) % e.cfg.vocab_size, c
    _wrap_decode(engines, change)


def _keep_state(engines):
    from repro.engine.engine import greedy_decode
    for e in engines:
        plain = jax.jit(functools.partial(greedy_decode, cfg=e.cfg))
        e._decode = lambda p, c, t, plain=plain: plain(p, c, t)[:2] + (c,)


def _drop_half_batch(engines):
    def change(f, e, p, c, t):
        return f(p, c, t.at[:e.max_batch // 2].set(0))
    _wrap_decode(engines, change)


def _one_replica_altered(engines):
    # replicas as slow as a chip's make the router spread the load over
    # all four: on the CPU a decode step takes a few milliseconds, below
    # the router's default estimate for a replica it has not timed yet,
    # so the first replica timed would take all of it
    def slow(f, e, p, c, t):
        time.sleep(0.05)
        return f(p, c, t)
    _wrap_decode(engines, slow)
    _alter_token(engines[-1:])


@pytest.mark.parametrize("name,fault", [
    (CELLS[0], _alter_token), (CELLS[0], _keep_state),
    (CELLS[0], _drop_half_batch), (CELLS[1], _keep_state),
    (rehearsal.REPLICAS, _one_replica_altered)])
def test_a_fault_in_the_timed_path_is_not_correct(small_root, name, fault):
    res = run(small_root, name, faults=fault)
    assert res["correct"] is False, res["check"]


@pytest.fixture(scope="module")
def tight_root(tmp_path_factory):
    # on the CPU the program multiplies in float32 as the reference does
    # and reads no gap; the limit lies between that and the control's
    return rehearsal.make_root(tmp_path_factory.mktemp("tight"),
                               gap_mean=1e-5)


@pytest.mark.parametrize("name", CELLS)
def test_the_control_in_the_programs_place_is_not_correct(tight_root, name):
    cell = harness.load_cell(name, tight_root)
    dev, seed = jax.devices()[0], 2 ** 31 + 7
    sv = harness.serve_phase(cell, seed, 3.0, False, [dev],
                             time.perf_counter(), log=lambda s: None)
    ok, table, ctrl = harness.decide(cell, sv.shapes, sv.rec, seed, dev,
                                     lambda s: None, ("int8",))
    assert ok is True, table
    assert ctrl["int8"][0] is False, ctrl["int8"][1]
