"""Benchmark harness: one module per paper figure/table.  Prints
``name,us_per_call,derived`` CSV rows (harness contract).

Per-figure argument parsing is defined ONCE: every suite declares its
slow/fast kwargs in the ``_Suite`` table below, and the shared
``--fast`` / ``--seed`` / ``--only`` flags are applied uniformly (the
scenario figures run through ``repro.bench.run_experiment``, so a
``--seed`` override reaches every spec the same way).

Usage:  PYTHONPATH=src python -m benchmarks.run \
            [--only fig2,fig13] [--fast] [--seed 7]
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
import traceback
from typing import Callable


@dataclasses.dataclass
class _Suite:
    fn: Callable                      # the figure's run()
    kw: dict = dataclasses.field(default_factory=dict)
    fast_kw: dict = dataclasses.field(default_factory=dict)
    seedable: bool = False            # accepts the shared --seed flag
    note: str = ""


def _suites(fast: bool) -> dict:
    from benchmarks import (fig1_iteration_latency, fig2_motivation,
                            fig6_end_to_end, fig7_ablation, fig8_predictor,
                            fig9_migration, fig10_sensitivity,
                            fig11_overhead, fig12_workflows,
                            fig13_autoscale, fig14_spot, fig15_rectify,
                            fig16_sharded, fig17_calibration,
                            fig18_fairness, fig19_disagg, fig20_learned)

    n_sim = 200 if fast else 400
    epochs = 12 if fast else 40

    return {
        "fig1": _Suite(fig1_iteration_latency.run),
        "fig2": _Suite(fig2_motivation.run, kw=dict(n=600),
                       fast_kw=dict(n=300), seedable=True),
        "fig6": _Suite(fig6_end_to_end.run,
                       kw=dict(n=n_sim,
                               scales=(1.0, 1.5, 2.0, 2.5, 3.0)),
                       fast_kw=dict(scales=(1.0, 2.0, 3.0))),
        "fig7": _Suite(fig7_ablation.run, kw=dict(n=n_sim)),
        "fig8": _Suite(fig8_predictor.run, kw=dict(epochs=epochs)),
        "fig9": _Suite(fig9_migration.run),
        "fig10": _Suite(fig10_sensitivity.run,
                        kw=dict(n=min(n_sim, 300),
                                epochs=max(epochs - 10, 8))),
        "fig11": _Suite(fig11_overhead.run),
        # fig12's sim is cheap (~40s); at n=40 the workflow sample is too
        # small for stable router ordering, so fast mode keeps n=60
        "fig12": _Suite(fig12_workflows.run, seedable=True),
        # fast mode halves the diurnal trace (first swell only): the
        # scale-up path is exercised, the trough-side drain is not
        "fig13": _Suite(fig13_autoscale.run, kw=dict(n=2200),
                        fast_kw=dict(n=1100), seedable=True),
        # fast mode halves the trace; the preemption rate is per-hour, so
        # the shorter span still sees eviction notices (asserted in-run)
        "fig14": _Suite(fig14_spot.run, kw=dict(n=2200),
                        fast_kw=dict(n=1100), seedable=True),
        # fast mode shortens the trace but keeps the mid-run drift point
        # (a fraction of the span, not an absolute time)
        "fig15": _Suite(fig15_rectify.run, kw=dict(n=2200),
                        fast_kw=dict(n=1000), seedable=True),
        # fast mode halves the sweep trace and swaps the ~1M-event /
        # 100-instance throughput run for a small one (the sweep's
        # multi-seed CIs and conflict assertions are kept either way)
        "fig16": _Suite(fig16_sharded.run, kw=dict(n=1200),
                        fast_kw=dict(n=600, full_trace=False),
                        seedable=True),
        # the sim is cheap (<1s/seed), so fast mode keeps the full trace
        # (a shorter diurnal span blunts the provision churn the figure
        # measures) and only trims the kernel microbench iterations
        "fig17": _Suite(fig17_calibration.run, kw=dict(n=900),
                        fast_kw=dict(fast=True), seedable=True),
        # fast mode halves the trace; the overload is a RATE (rps is
        # kept), so the abuser's starvation effect survives the cut —
        # the in-run retention assertions hold either way
        "fig18": _Suite(fig18_fairness.run, kw=dict(n=3200),
                        fast_kw=dict(n=1600), seedable=True),
        # fast mode cuts the trace to a third; the colocated arm's
        # chunked-prefill interference and the naive arm's WAN handoffs
        # are per-request effects, so the margins survive the cut (the
        # in-run gp/$ and WAN-penalty assertions hold either way)
        "fig19": _Suite(fig19_disagg.run, kw=dict(n=1500),
                        fast_kw=dict(n=500), seedable=True),
        # fast mode keeps the full trace (the warm-start needs the
        # training signal; the sim is cheap) and trims eval seeds plus
        # two of the three off-policy certification replays
        "fig20": _Suite(fig20_learned.run,
                        fast_kw=dict(n_seeds=2, fast=True),
                        seedable=True),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--fast", action="store_true",
                    help="smaller workloads / fewer epochs")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the workload seed of every "
                         "seed-accepting scenario suite")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    suites = _suites(args.fast)
    only = [s for s in args.only.split(",") if s]
    failed = []
    ran = []
    for name, suite in suites.items():
        if only and name not in only:
            continue
        kw = dict(suite.kw)
        if args.fast:
            kw.update(suite.fast_kw)
        if suite.seedable and args.seed is not None:
            kw["seed"] = args.seed
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        try:
            suite.fn(**kw)
        except Exception:
            failed.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        ran.append(name)
    if args.fast and "fig16" not in ran:
        # the event-loop throughput line: cheap enough to always report
        # in fast mode, even when the fig16 sweep itself was filtered out
        from benchmarks.fig16_sharded import throughput_line
        print("# --- event-loop throughput ---", flush=True)
        try:
            throughput_line(fast=True)
        except Exception:
            failed.append("eventloop")
            traceback.print_exc()
    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)
    print("# all benchmark suites complete")


if __name__ == "__main__":
    main()
