"""What the engine records of itself, as the benchmark sees it: the
queue wait and the time from admission to first token read from the
engine's stamps on each request (``queue_wait_p90``,
``first_token_ms``), and, in a traced rehearsal on the CPU, the
engine's spans on the trace's clock inside the harness's step spans."""
import jax
import pytest

from chipbench import harness
from chipbench import trace as tracelib
from chipbench.harness import Record, Run
from chipbench.tests import rehearsal
from chipbench.tests.test_harness import CELLS

ENGINE_SPANS = ("engine.admit", "engine.prefill", "engine.sync",
                "engine.splice", "engine.inputs", "engine.decode",
                "engine.commit")
# The harness reads its anchor from the host clock just before the
# anchor span opens, so host times land a few microseconds late on the
# trace's clock; 50 us is far below a step (milliseconds) and still
# catches two clocks that disagree
CLOCK_TOL_NS = 50e3


class _Step:
    counted, req = True, None


def _run(waits, end=11.0):
    from repro.engine.engine import EngineRequest
    rec = Record(steps=[], t0=10.0, seconds=0.2, end=end)
    for k, wait in enumerate(waits):
        s = _Step()
        s.req = EngineRequest(rid=k, tokens=[1], prompt_len=1)
        s.req.t_submit = 10.0 + 0.01 * k
        s.req.t_admit = None if wait is None else s.req.t_submit + wait
        rec.steps.append(s)
    return Run(None, rec, 1.0, None, {}, None)


def test_queue_wait_p90_reads_the_engines_stamps():
    # nineteen waits of 1-19 ms and a step never admitted, which waits
    # until the run stops (0.81 s after its submission)
    run = _run([0.001 * i for i in range(1, 20)] + [None])
    assert harness.read_metric("queue_wait_p90", run) == pytest.approx(
        0.018)
    for s in run.rec.steps[-3:-1]:                 # 18, 19 ms: not counted
        s.counted = False
    run.rec.steps[-1].req.t_admit = 10.99          # admitted after 0.8 s
    assert harness.read_metric("queue_wait_p90", run) == pytest.approx(
        0.017)


def test_queue_wait_p90_reads_nothing_from_an_engine_without_stamps():
    run = _run([0.001, 0.002])
    for s in run.rec.steps:
        s.req = type("Req", (), {"t_admit": 0.0})()
    assert harness.read_metric("queue_wait_p90", run) is None
    assert harness.read_metric("queue_wait_p90", _run([])) is None


def test_first_token_ms_reads_the_engines_stamps():
    # admitted at once; first tokens 1-19 ms after admission, and one
    # admitted step with no first token, which counts until the run stops
    run = _run([0.0] * 20)
    for k, s in enumerate(run.rec.steps[:-1]):
        s.req.t_first = s.req.t_admit + 0.001 * (k + 1)
    assert harness.read_metric("first_token_ms", run) == pytest.approx(
        18.0)
    for s in run.rec.steps[-3:-1]:                 # 18, 19 ms: not counted
        s.counted = False
    run.rec.steps[-1].req.t_first = run.rec.steps[-1].req.t_admit + 0.5
    assert harness.read_metric("first_token_ms", run) == pytest.approx(
        17.0)


def test_first_token_ms_leaves_out_steps_never_admitted():
    run = _run([0.0, 0.0, None])
    for s in run.rec.steps[:2]:
        s.req.t_first = s.req.t_admit + 0.004
    assert harness.read_metric("first_token_ms", run) == pytest.approx(4.0)
    assert harness.read_metric("first_token_ms", _run([None])) is None


def test_first_token_ms_reads_nothing_from_an_engine_without_stamps():
    run = _run([0.001, 0.002])
    for s in run.rec.steps:
        s.req = type("Req", (), {"t_admit": 0.0})()
    assert harness.read_metric("first_token_ms", run) is None
    assert harness.read_metric("first_token_ms", _run([])) is None


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", [CELLS[0], rehearsal.REPLICAS])
def test_engine_spans_lie_inside_the_harness_steps(small_root, name,
                                                   monkeypatch):
    """A traced rehearsal, with the trace's reduction told to keep the
    engine's spans: every one of them lies, through ``trace.at``, inside
    one of the harness's step spans (``rec.step_spans``), so the host
    clock and the trace's agree; the queue wait and the time to first
    token read."""
    monkeypatch.setattr(tracelib, "HOST_SPANS",
                        tracelib.HOST_SPANS + ENGINE_SPANS)
    cell = harness.load_cell(name, small_root)
    devices = [jax.devices()[0]] * cell.chips
    sv = harness.serve_phase(cell, 2 ** 31 + 5, 3.0, True, devices, 0.0,
                             log=lambda s: None)
    tr, rec = sv.trace, sv.rec
    steps = sorted((tr.at(a), tr.at(t)) for _, a, t in rec.step_spans)
    spans = sorted((s for s in tr.spans if s[0] in ENGINE_SPANS),
                   key=lambda s: s[1])
    assert {s[0] for s in spans} == set(ENGINE_SPANS)
    i = 0
    for name_, a, b in spans:
        while i + 1 < len(steps) and steps[i + 1][0] - CLOCK_TOL_NS <= a:
            i += 1
        lo, hi = steps[i]
        assert lo - CLOCK_TOL_NS <= a and b <= hi + CLOCK_TOL_NS, name_
    run = Run(cell, rec, sv.setup_s, cell.module("cost"), {}, tr)
    assert harness.read_metric("queue_wait_p90", run) >= 0
    assert harness.read_metric("first_token_ms", run) > 0
