#!/usr/bin/env python3
"""Measure a cell's solo latencies and sweep its arrival rate, in one
process on the chips of this host.  This is how the numbers in a cell's
file were found; the benchmark's runs never call it.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 0.3,0.4,0.5 [--solo]

``--solo`` serves one request of every prompt length alone on the first
engine (32 output tokens) and fits the time to first token as
``base + per_token * prompt`` and the time per output token.  Each rate
then gets a window of its own on the same engines (lead-in first,
drained between rates), with deadlines from the measured solo latencies when ``--solo``
is given and from the cell's file otherwise.  One JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def solo(engine, lens, vocab, out=32):
    """(ttft_base_s, ttft_per_token_s, tpot_s) of requests served alone."""
    import numpy as np
    from repro.engine.engine import EngineRequest
    rng = np.random.default_rng(1)
    rows, tpots = [], []
    for n in sorted(lens):
        req = EngineRequest(rid=0, tokens=[int(t) for t in rng.integers(
            0, vocab, n)], prompt_len=n, max_new_tokens=out)
        engine.submit(req)
        t0, times = time.perf_counter(), []
        while not req.done:
            k = len(req.tokens)
            engine.step()
            times += [time.perf_counter()] * (len(req.tokens) - k)
        engine.completed.clear()
        engine.drain_events()
        rows.append((n, times[0] - t0))
        tpots += list(np.diff(times))
    x = np.array([r[0] for r in rows], float)
    y = np.array([r[1] for r in rows], float)
    slope, base = np.polyfit(x, y, 1) if len(x) > 1 else (0.0, y[0])
    return {"ttft_base_s": float(base), "ttft_per_token_s": float(slope),
            "tpot_s": float(np.median(tpots)),
            "ttft_by_prompt": [[int(a), float(b)] for a, b in rows]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--solo", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from chipbench import harness, stats, workload
    from repro.launch.compile_cache import setup_compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.exit(f"needs {cell.chips} TPU chips, found {devices}")
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    serve, vocab = cell.spec["serve"], cell.spec["vocab_size"]
    cfg = cell.module("program").config(cell.spec)
    engines, _ = harness.build_engines(cell, cfg, devices[:cell.chips],
                                       args.seed)
    lens = workload.prompt_buckets(cell.mix, serve["max_len"])
    harness.warm_up(engines, lens, vocab)
    sol = cell.params["solo"]
    if args.solo:
        sol = solo(engines[0], lens, vocab)
        print(json.dumps({"solo": sol}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        wfs = workload.make_workflows(cell.mix, rate, args.seconds,
                                      serve["max_len"], vocab, args.seed + i)
        rec = harness.serve_window(engines, wfs, args.seconds,
                                   lambda s: workload.deadline(
                                       s, sol, cell.mix["slo_scale"]))
        while any(e.pending for e in engines):
            for e in engines:
                e.step()
                e.drain_events()
        for e in engines:
            e.completed.clear()
        counted = [s for s in rec.steps if s.counted]
        met = sum(stats.met_deadline(s) for s in counted)
        T = args.seconds
        thirds = [[b for t, b in rec.backlog
                   if rec.t0 + k * T / 3 <= t < rec.t0 + (k + 1) * T / 3]
                  for k in range(3)]
        tt = stats.ttfts(rec.steps, rec.end)
        g = stats.token_gaps(rec.steps)
        print(json.dumps({
            "rate_wps": rate, "steps": len(counted),
            "attainment": met / max(len(counted), 1),
            "goodput": stats.goodput(rec.steps, T),
            "out_tok_s": stats.tokens_in(rec.steps, rec.t0, rec.t0 + T) / T,
            "ttft_p90": stats.percentile(tt, 90) if tt else None,
            "tpot_p95_ms": 1e3 * stats.percentile(g, 95) if g else None,
            "tpot_p99_ms": 1e3 * stats.percentile(g, 99) if g else None,
            "backlog_by_third": [float(np.mean(b)) if b else None
                                 for b in thirds],
            "drain_s": rec.end - rec.t0 - T,
            "routed": rec.routed}), flush=True)


if __name__ == "__main__":
    main()
