"""Latency-profile harness: the operational front end of
``repro.bench.profile``.

Derives (or measures) one :class:`LatencyProfile` artifact per
(hardware, model) pair and writes it under ``--out`` as
``<hardware>__<model>.json`` — the file the simulator's
``Cluster(profiles=...)`` and the fig1 overlay consume.

Modes:

* default        — analytic profiles from the catalog roofline constants
  (provenance ``analytic``): the calibration scaffold CI smokes, and the
  fallback wherever no accelerator is attached;
* ``--engine``   — measure a real :class:`InferenceEngine` on this host's
  TPU (provenance ``measured-tpu``) at the model's full config; it fails
  where JAX finds no TPU;
* ``--kernel-bench`` — the paged-attention tiling microbench
  (before/after ``pages_per_tile``), reported as CSV rows.

  PYTHONPATH=src python -m benchmarks.profile \
      [--hardware A800,H800] [--model llama3.1-8b] [--out results/profiles]
      [--engine] [--kernel-bench]
"""
from __future__ import annotations

import argparse
import pathlib

from benchmarks.common import emit
from repro.bench.profile import (analytic_profile, measure_engine_profile,
                                 paged_kernel_microbench)
from repro.cluster import hardware as hwlib
from repro.launch.compile_cache import setup_compile_cache


def _row(name: str, prof) -> None:
    b = prof.decode_batches[min(3, len(prof.decode_batches) - 1)]
    c = prof.decode_ctxs[len(prof.decode_ctxs) // 2]
    n = prof.prefill_tokens[-1]
    tok_s = n / max(prof.prefill_time(n) - prof.overhead_s, 1e-12)
    emit(name, 0.0,
         f"{prof.provenance}: d(b={b},ctx={c:.0f})="
         f"{prof.decode_time(b, c) * 1e3:.2f}ms "
         f"prefill={tok_s:.0f}tok/s overhead={prof.overhead_s * 1e3:.1f}ms")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hardware", default="A800",
                    help="comma-separated catalog names (on-demand or spot)")
    ap.add_argument("--model", default="llama3.1-8b")
    ap.add_argument("--out", default="results/profiles",
                    help="artifact directory (created if missing)")
    ap.add_argument("--engine", action="store_true",
                    help="measure a real InferenceEngine on this host's "
                         "TPU instead of deriving analytic rows")
    ap.add_argument("--kernel-bench", action="store_true",
                    help="also run the paged-attention tiling microbench")
    args = ap.parse_args(argv)
    setup_compile_cache()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    fp = hwlib.footprint(args.model)
    for name in [h for h in args.hardware.split(",") if h]:
        hw = hwlib.catalog(name)
        if args.engine:
            import jax
            from repro.configs import get_config
            if jax.default_backend() != "tpu":
                raise SystemExit("--engine measures a TPU; JAX found "
                                 f"{jax.default_backend()!r}")
            prof = measure_engine_profile(get_config(args.model), hw)
        else:
            prof = analytic_profile(hw, fp)
        path = outdir / f"{name}__{args.model}.json"
        prof.save(path)
        _row(f"profile_{name}_{args.model}", prof)
        print(f"# wrote {path}")

    if args.kernel_bench:
        mb = paged_kernel_microbench()
        emit("profile_paged_tiling", mb["tiled_us"],
             f"steps={mb['speedup_steps']:.2f}x "
             f"wall={mb['speedup_wall']:.2f}x "
             f"max_err={mb['max_err_tiled']:.2e}")


if __name__ == "__main__":
    main()
