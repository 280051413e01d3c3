"""Serving launcher: GoodServe's proxy routing requests across real JAX
inference engines, one engine per device.

By default it serves ``--arch`` at its published widths, with the depth
cut to what one TPU v5e chip holds (``DEPTH_CUTS``), one engine per
chip.  ``--reduced`` serves the smoke-test size instead: the CPU
rehearsal, where every engine shares the one CPU device.

  PYTHONPATH=src python -m repro.launch.serve --engines 1
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --reduced --engines 2
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Sequence

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.configs.base import ModelConfig, uniform_stage
from repro.core.estimator import EMAEstimator
from repro.engine.engine import EngineRequest, InferenceEngine
from repro.launch.compile_cache import setup_compile_cache


@dataclasses.dataclass(frozen=True)
class DepthCut:
    layers: int
    why: str


# Engine precision (float32) at CHIP_BATCH x CHIP_MAX_LEN with the decode
# cache donated; sizes from compiled.memory_analysis() of the decode step
# compiled for TPU v5e, which leaves 15.75 GB of its 16 GB to programs.
DEPTH_CUTS = {
    "minicpm-2b": DepthCut(
        20, "one decode step of all 40 layers needs 17.5 GB of device "
            "memory against the 15.75 GB of one v5e chip; 20 layers need "
            "11.5 GB"),
}
CHIP_BATCH, CHIP_MAX_LEN = 8, 1024
CHIP_PROMPT_LENS = (64, 256, 512)
REDUCED_BATCH, REDUCED_MAX_LEN = 4, 96
REDUCED_PROMPT_LENS = (8, 16, 24)


def chip_config(arch: str) -> ModelConfig:
    """``arch`` at its published widths, cut in depth to one chip."""
    if arch not in DEPTH_CUTS:
        raise ValueError(f"no one-chip depth is settled for {arch!r}; "
                         f"settled: {sorted(DEPTH_CUTS)}")
    n = DEPTH_CUTS[arch].layers
    return dataclasses.replace(get_config(arch), num_layers=n,
                               stages=uniform_stage(n))


def make_requests(cfg: ModelConfig, n: int, prompt_lens: Sequence[int],
                  max_new: int, seed: int,
                  keep_logits: bool = False) -> List[EngineRequest]:
    """``n`` random-token requests from ``seed``; prompt lengths cycle
    through ``prompt_lens`` (prefill compiles once per length)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        prompt = [int(t) for t in rng.integers(
            0, cfg.vocab_size, prompt_lens[rid % len(prompt_lens)])]
        reqs.append(EngineRequest(rid=rid, tokens=prompt,
                                  prompt_len=len(prompt),
                                  max_new_tokens=max_new,
                                  keep_logits=keep_logits))
    return reqs


def build_engines(cfg: ModelConfig, devices, *, max_batch: int,
                  max_len: int, seed: int = 0) -> List[InferenceEngine]:
    """One engine on each of ``devices``, all initialised from ``seed``:
    replicas of one model."""
    return [InferenceEngine(cfg, max_batch=max_batch, max_len=max_len,
                            seed=seed, device=d) for d in devices]


def route(engines: Sequence[InferenceEngine], est: EMAEstimator) -> int:
    """The engine with the least expected wait: its EMA decode time
    scaled by the work already queued or running on it."""
    return min(range(len(engines)),
               key=lambda i: est.snapshot(i).d * (1 + engines[i].pending))


@dataclasses.dataclass
class ServeResult:
    completed: List[EngineRequest]
    served_by: Dict[int, int]          # rid -> engine index
    seconds: float
    est: EMAEstimator

    @property
    def tokens(self) -> int:
        return sum(len(r.generated) for r in self.completed)


def serve(engines: Sequence[InferenceEngine],
          requests: Sequence[EngineRequest]) -> ServeResult:
    """Route ``requests`` across fresh ``engines`` and step every engine
    until all of them complete, feeding each engine's timing events to
    the EMA estimator the router reads."""
    est = EMAEstimator()
    served_by = {}
    for req in requests:
        gid = route(engines, est)
        engines[gid].submit(req)
        served_by[req.rid] = gid
    t0 = time.perf_counter()
    while sum(len(e.completed) for e in engines) < len(requests):
        for gid, eng in enumerate(engines):
            eng.step()
            for kind, size, dt in eng.drain_events():
                if kind == "decode":
                    est.observe_decode_iter(gid, dt)
                else:
                    est.observe_prefill(gid, size, dt)
    seconds = time.perf_counter() - t0
    completed = [r for e in engines for r in e.completed]
    return ServeResult(completed, served_by, seconds, est)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--engines", type=int, default=1)
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the smoke-test size (the CPU rehearsal) "
                         "instead of published widths")
    args = ap.parse_args(argv)
    setup_compile_cache()

    devices = jax.devices()
    if args.reduced:
        cfg = reduce_config(get_config(args.arch))
        batch, max_len, lens = (REDUCED_BATCH, REDUCED_MAX_LEN,
                                REDUCED_PROMPT_LENS)
        devices = [devices[i % len(devices)] for i in range(args.engines)]
    else:
        cfg = chip_config(args.arch)
        batch, max_len, lens = CHIP_BATCH, CHIP_MAX_LEN, CHIP_PROMPT_LENS
        if args.engines > len(devices):
            raise SystemExit(f"one engine per chip: {args.engines} engines "
                             f"asked for, {len(devices)} devices found")
        devices = devices[:args.engines]
    engines = build_engines(cfg, devices, max_batch=batch, max_len=max_len)
    res = serve(engines, make_requests(cfg, args.n_requests, lens,
                                       args.max_new, seed=0))
    print(f"served {len(res.completed)} requests of {cfg.name} "
          f"({cfg.num_layers}L d={cfg.d_model}), {res.tokens} tokens in "
          f"{res.seconds:.1f}s across {len(engines)} engines on "
          f"{devices[0].platform}")
    for gid, eng in enumerate(engines):
        e = res.est.snapshot(gid)
        print(f"  engine{gid}: served={len(eng.completed)} "
              f"d_ema={e.d * 1e3:.1f}ms/tok p_ema={e.p * 1e6:.0f}us/tok")


if __name__ == "__main__":
    main()
