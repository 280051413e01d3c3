"""The engine's own instrumentation, on the CPU: the spans a step emits
under the profiler (names, order, nesting, arguments), the host times
stamped on each request, and the names its programs carry into the
trace."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.configs import get_config, reduce_config
from repro.engine.engine import (DECODE, PREFILL, PREFILL_CHUNK,
                                 EngineRequest, InferenceEngine)

CFG = reduce_config(get_config("llama3.1-8b"))


def _requests(n, plen=7, new=4):
    return [EngineRequest(rid=i, tokens=list(range(2 + i, 2 + i + plen)),
                          prompt_len=plen, max_new_tokens=new)
            for i in range(n)]


def _traced(tmp_path, body):
    """Run ``body`` under the profiler; the program's spans and the
    harness-style ``engine.step`` spans it recorded, as (name, start,
    end, arguments) sorted by start, the outer first."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith("engine.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _warm(eng):
    for r in _requests(2, new=2):
        r.rid += 100
        eng.submit(r)
    eng.run_until_drained()


def test_a_step_emits_its_spans_in_order_and_nested(tmp_path):
    eng = InferenceEngine(CFG, max_batch=2, max_len=48)
    _warm(eng)
    for r in _requests(2):
        eng.submit(r)

    def one_step():
        with TraceAnnotation("engine.step"):
            eng.step()

    spans = _traced(tmp_path, one_step)
    assert [s[0] for s in spans] == [
        "engine.step",
        "engine.admit", "engine.prefill", "engine.sync", "engine.splice",
        "engine.admit", "engine.prefill", "engine.sync", "engine.splice",
        "engine.inputs", "engine.decode", "engine.sync", "engine.commit"]
    step, prog = spans[0], spans[1:]
    for s in prog:
        assert _inside(s, step)
        assert s[3]["dev"] == eng.device.id
    admits = [s for s in prog if s[0] == "engine.admit"]
    assert [a[3]["rid"] for a in admits] == [0, 1]
    assert all(a[3]["prompt_len"] == 7 and a[3]["queued_ms"] >= 0
               for a in admits)
    for k, a in enumerate(admits):            # each holds its own three
        kids = prog[4 * k + 1:4 * k + 4]
        assert all(_inside(c, a) for c in kids)
    for a, b in zip(prog[8:], prog[9:]):      # the decode path, in turn
        assert a[2] <= b[1] and not _inside(a, admits[-1])


def test_a_chunked_admission_spans_each_chunk(tmp_path):
    eng = InferenceEngine(CFG, max_batch=2, max_len=48, prefill_chunk=4)
    _warm(eng)
    eng.submit(_requests(1)[0])

    def two_steps():
        for _ in range(2):                    # 7 prompt tokens: 2 chunks
            with TraceAnnotation("engine.step"):
                eng.step()

    names = [s[0] for s in _traced(tmp_path, two_steps)]
    assert names == [
        "engine.step", "engine.admit", "engine.prefill",
        "engine.step", "engine.admit", "engine.prefill", "engine.splice",
        "engine.sync", "engine.inputs", "engine.decode", "engine.sync",
        "engine.commit"]


@pytest.mark.parametrize("chunk", [None, 4])
def test_requests_are_stamped_submit_admit_first(chunk):
    eng = InferenceEngine(CFG, max_batch=2, max_len=48, prefill_chunk=chunk)
    reqs = _requests(3)                       # the third waits for a slot
    for r in reqs:
        assert (r.t_submit, r.t_admit, r.t_first) == (None, None, None)
        eng.submit(r)
        assert r.t_submit is not None and r.t_admit is None
    eng.run_until_drained()
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first
    assert reqs[2].t_admit > reqs[0].t_first


def test_engine_programs_carry_their_names():
    eng = InferenceEngine(CFG, max_batch=2, max_len=48)
    toks = np.zeros((2, 1), np.int32)
    prompt = np.zeros((1, 8), np.int32)
    staged = eng._staging_cache()
    lowered = {
        "jit_engine_decode": DECODE.lower(eng.params, eng.cache, toks,
                                          cfg=CFG, ctx=eng.ctx),
        "jit_engine_prefill": PREFILL.lower(eng.params, prompt, cfg=CFG,
                                            max_len=48, ctx=eng.ctx),
        "jit_engine_prefill_chunk": PREFILL_CHUNK.lower(
            eng.params, staged, prompt, np.asarray([8], np.int32), cfg=CFG,
            ctx=eng.ctx)}
    for name, low in lowered.items():
        assert f"module @{name} " in low.as_text()
