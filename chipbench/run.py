#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this host.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell is an entry of ``BENCHMARK.json``.  With ``--trace 0`` the last
line of stdout is the result with the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window and the line carries the
per-layer metrics, the device's busy seconds and a breakdown.  Where JAX
finds no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# JAX's persistent compilation cache lives at one fixed place inside the
# checkout, whatever the environment says: only the first run of a cell
# in a checkout compiles, and two checkouts share nothing
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's backend is {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    print(f"JAX found its devices {time.perf_counter() - START:.3f} s "
          f"after the start", file=sys.stderr)
    if len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"device {devices[0].device_kind} x {len(devices)}, compile "
          f"cache {cache}", file=sys.stderr)

    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace),
        devices[:cell.chips], START,
        log=lambda s: print(s, file=sys.stderr, flush=True))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
