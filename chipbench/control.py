#!/usr/bin/env python3
"""The readings that a cell's check limits are set from, in one process
on the chips of this host.  The benchmark's runs never call it.

  python3 chipbench/control.py --workload <cell> --seconds <s> \
      --seeds 1,2,3 [--control-seeds 1,2,3]

For every seed it serves a window of the cell (its rate, sizes and
traffic), frees the program, and decides ``correct`` for what the
program served, as a benchmark run does (the lower readings).  For the
control seeds it also decides it for each control put in the program's
place at each position of the same prompts and served tokens, under the
same limits (``harness.decide``): ``int8``, the reference one precision
below the stated bfloat16 (the upper readings; it has to come out not
correct), and ``bf16``, the reference wholly in bfloat16.  One JSON line
per seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    import jax
    from chipbench import harness
    from repro.launch.compile_cache import setup_compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.exit(f"needs {cell.chips} TPU chips, found {devices}")
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctrl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        sv = harness.serve_phase(cell, seed, args.seconds, False,
                                 devices[:cell.chips], t, log=log)
        controls = ("int8", "bf16") if seed in ctrl_seeds else ()
        t1 = time.perf_counter()
        out = harness.decide(cell, sv.shapes, sv.rec, seed, devices[0], log,
                             controls)
        line = {"seed": seed, "correct": out[0], "check": out[1],
                "check_s": time.perf_counter() - t1}
        if controls:
            line["controls"] = {m: {"correct": ok, "check": table}
                                for m, (ok, table) in out[2].items()}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
