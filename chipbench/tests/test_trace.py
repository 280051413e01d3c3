"""The reduction from a profiler trace to metrics, on small made-up
traces: busy union, own times of nested operations, idle gaps named by
the host's spans, the attribution of programs to decode and prefill, and
the per-layer readers built on them."""
import json

import pytest

from chipbench import harness
from chipbench import trace as tracelib
from chipbench import work
from chipbench.cost import llama_dense
from chipbench.harness import Record, Run

MS = 1e6  # nanoseconds


def _trace():
    # one device; the host's anchor span opens at 0 ns = host time 10.0 s
    ops = {"/device:TPU:0": [
        ("%while.1 while (tuple)", 0 * MS, 10 * MS),
        ("%fusion.2 fusion f32[8]", 1 * MS, 4 * MS),      # inside the while
        ("%convert.3 convert bf16[4]", 12 * MS, 14 * MS),
        ("%fusion.2 fusion f32[8]", 20 * MS, 30 * MS),
    ]}
    modules = {"/device:TPU:0": [
        ("jit__unknown(1)", 0 * MS, 10 * MS),              # prefill
        ("jit_splice_cache(2)", 10.5 * MS, 11 * MS),       # its splice
        ("jit__unknown(3)", 12 * MS, 14 * MS),             # decode
        ("jit__unknown(3)", 20 * MS, 30 * MS),             # decode
    ]}
    spans = [("anchor", 0.0, 0.0), ("engine.step", 0 * MS, 15 * MS),
             ("client", 15 * MS, 16 * MS), ("wait", 16 * MS, 19.5 * MS),
             ("engine.step", 19.5 * MS, 31 * MS)]
    tr = tracelib.Trace(ops, modules, spans)
    tr.anchor(10.0)
    return tr


def test_anchor_puts_host_time_on_the_trace_clock():
    tr = _trace()
    assert tr.at(10.0) == pytest.approx(0.0)
    assert tr.at(10.02) == pytest.approx(20 * MS)
    with pytest.raises(ValueError):
        tracelib.Trace({}, {}, []).anchor(0.0)


def test_busy_is_the_union_of_operations():
    assert work.coverage(_run()) == (pytest.approx(0.022),
                                     pytest.approx(0.04))
    run = _run()                      # the window 5-25 ms
    run.rec.t0, run.rec.seconds = 10.005, 0.02
    assert work.coverage(run) == (pytest.approx(0.012), pytest.approx(0.02))
    assert tracelib.union([("a", 0, 2), ("b", 1, 3), ("c", 5, 6)]) == [
        (0, 3), (5, 6)]


def test_top_ops_count_own_time_of_nested_operations():
    top = dict(tracelib.top_ops(_trace(), 0, 40 * MS))
    assert top["%while.1 while (tuple)"] == pytest.approx(0.007)
    assert top["%fusion.2 fusion f32[8]"] == pytest.approx(0.013)
    assert top["%convert.3 convert bf16[4]"] == pytest.approx(0.002)


def test_idle_gaps_are_named_by_the_host_span_around_them():
    gaps = tracelib.idle_gaps(_trace(), 0, 40 * MS)
    assert gaps[0] == ("host (no span)", pytest.approx(0.010))   # 30-40 ms
    assert gaps[1] == ("wait", pytest.approx(0.006))             # 14-20 ms
    assert gaps[2] == ("engine.step", pytest.approx(0.002))      # 10-12 ms
    assert tracelib.idle_by_host(_trace(), 0, 40 * MS)["wait"] == \
        pytest.approx(0.006)


def test_op_name_keeps_name_opcode_and_shape():
    assert tracelib.op_name(
        "%fusion.69 = f32[16,122880]{1,0:T(8,128)} fusion(f32[1]{0} %p), "
        "kind=kOutput") == "%fusion.69 fusion f32[16,122880]"
    assert tracelib.op_name("%while.3 = (s32[]{:T(128)}, f32[2]{0}) "
                            "while((s32[]{:T(128)}) %t)") == \
        "%while.3 while (tuple)"


SPEC = json.loads((harness.ROOT / "chipbench" / "configs"
                   / "minicpm-2b.json").read_text())
PEAK = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9}


def _run(trace=True, lose_last=False):
    rec = Record(steps=[], t_load=10.0, t0=10.0, seconds=0.04,
                 planes=["/device:TPU:0"])
    rec.step_spans = [(0, 10.0, 10.015), (0, 10.0195, 10.031)]
    rec.decodes = [(0, 10.015, [500, 700]), (0, 10.031, [501, 701])]
    rec.events = [(0, "prefill", 512, 0.010, 10.015),
                  (0, "decode", 2, 0.004, 10.015),
                  (0, "decode", 2, 0.011, 10.031)]
    cell = harness.Cell("c", 1, dict(SPEC, serve={"max_batch": 4}), {}, {},
                        [], [])
    tr = _trace() if trace else None
    if lose_last:      # the profiler dropped the second step's program
        tr.modules["/device:TPU:0"].pop()
    return Run(cell, rec, 1.0, llama_dense, PEAK, tr)


def test_programs_are_attributed_to_decode_and_prefill():
    dev = work.device_s(_run())
    assert dev["decode"] == pytest.approx(0.012)
    assert dev["prefill"] == pytest.approx(0.0105)
    assert work.device_s(_run(trace=False)) is None


def test_trace_readers_divide_model_work_by_device_time():
    run = _run()
    flops, least = work.decode(run)
    f1, b1 = llama_dense.decode(SPEC, [500, 700])
    f2, b2 = llama_dense.decode(SPEC, [501, 701])
    assert flops == pytest.approx(f1 + f2)
    assert least == pytest.approx(max(f1 / 197e12, b1 / 819e9)
                                  + max(f2 / 197e12, b2 / 819e9))
    roof = harness.read_metric("decode_roofline", run)
    assert roof == pytest.approx(100 * least / 0.012)
    assert harness.read_metric("decode_mfu", run) == pytest.approx(
        100 * flops / (0.012 * 197e12))
    pf, pb = llama_dense.prefill(SPEC, 512)
    assert harness.read_metric("prefill_roofline", run) == pytest.approx(
        100 * max(pf / 197e12, pb / 819e9) / 0.0105)
    assert harness.read_metric("idle_share", run) == pytest.approx(
        100 * (1 - 0.022 / 0.04))
    # without a trace the device readers find nothing, and say so
    for name in ("decode_roofline", "prefill_mfu", "idle_share"):
        assert harness.read_metric(name, _run(trace=False)) is None


def test_a_step_whose_programs_the_trace_lost_is_left_out():
    """The second step decoded (its engine says so) but no program of it
    is in the trace: its span leaves the traced window, the operations
    inside it leave the busy time, and its work is not counted."""
    whole, run = _run(), _run(lose_last=True)
    assert work.lost_steps(whole)[0] == set()
    lost, spans = work.lost_steps(run)
    assert lost == {(0, 10.031)}
    assert spans["/device:TPU:0"] == [
        (pytest.approx(19.5 * MS), pytest.approx(31 * MS))]
    assert work.coverage(whole) == (pytest.approx(0.022),
                                    pytest.approx(0.04))
    busy, window = work.coverage(run)
    assert window == pytest.approx(0.04 - 0.0115)
    assert busy == pytest.approx(0.012)          # the 20-30 ms op is out
    f1, _ = llama_dense.decode(SPEC, [500, 700])
    assert work.decode(run)[0] == pytest.approx(f1)
    assert harness.read_metric("idle_share", run) == pytest.approx(
        100 * (1 - 0.012 / 0.0285))


def test_engine_event_readers():
    run = _run(trace=False)
    assert harness.read_metric("batch_occupancy", run) == pytest.approx(50)
    assert harness.read_metric("decode_step_ms", run) == pytest.approx(7.5)
    assert harness.read_metric("prefill_stall_share", run) == \
        pytest.approx(100 * 0.010 / 0.025)
    assert harness.read_metric("prefill_ms_per_ktok", run) == \
        pytest.approx(0.010 / 0.512 * 1e3)


def test_cost_counts_weights_once_and_context_per_request():
    f0, b0 = llama_dense.decode(SPEC, [])
    f1, b1 = llama_dense.decode(SPEC, [1000])
    L, kvd = SPEC["num_hidden_layers"], 36 * 64
    nb = llama_dense.DTYPE_BYTES[SPEC["dtype"]]
    assert SPEC["dtype"] == "bfloat16" and nb == 2
    assert f0 == 0 and b0 > nb * 1.4e9            # ~1.5 B weights
    assert b1 - b0 == pytest.approx(nb * L * 2 * kvd * 1000)
