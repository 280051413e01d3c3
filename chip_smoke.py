#!/usr/bin/env python3
"""Chip smoke: serve MiniCPM-2B at its published widths on TPU v5e through
the serving launcher's own loop (``repro.launch.serve.serve``), and check
what comes out.

  python chip_smoke.py               # one chip: 8 requests; engine logits
                                     # checked against model_forward
  python chip_smoke.py --replicas 4  # only: four one-chip engines behind
                                     # the router vs the same requests
                                     # served by one engine

Weights and prompts are random, made from ``--seed``.  Where JAX finds no
TPU the smoke exits non-zero and prints no result; it never falls back to
the CPU.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
from typing import Dict, List, Sequence

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.engine.engine import EngineRequest  # noqa: E402
from repro.launch import serve as launcher  # noqa: E402
from repro.launch.compile_cache import (CompileLog,  # noqa: E402
                                        setup_compile_cache)
from repro.models import model_forward  # noqa: E402
from repro.models.model import logits_fn  # noqa: E402

ARCH = "minicpm-2b"
N_REQUESTS, MAX_NEW = 8, 32
# Logit error is the relative L2 distance of a logit row from its
# reference, the largest over rows.  On TPU, float32 matmuls at default
# precision round their operands to bfloat16 (relative step 2**-8); the
# engine (prefill, then decode through the cache) and model_forward (the
# whole sequence at once) round different intermediates, and the
# difference compounds over the layers.  On a 20-layer d_model=256 copy
# of the model (float32, CPU), rounding every weight to bfloat16 moves
# rows by 1.0-1.4%, while changing one context token moves them by
# 20-140%.  5% covers the rounding and stays far below the O(0.1-1) rows
# that a wrong cache slot, position or mask gives.
LOGIT_TOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Size:
    cfg: ModelConfig
    max_batch: int
    max_len: int
    prompt_lens: Sequence[int]


def chip_size() -> Size:
    return Size(launcher.chip_config(ARCH), launcher.CHIP_BATCH,
                launcher.CHIP_MAX_LEN, launcher.CHIP_PROMPT_LENS)


def logit_error(got: np.ndarray, ref: np.ndarray) -> float:
    """max over rows t of |got_t - ref_t| / |ref_t| (L2 norms)."""
    return float(np.max(np.linalg.norm(got - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


@functools.partial(jax.jit, static_argnums=1)
def _forward_logits(params, cfg: ModelConfig, tokens):
    h, _ = model_forward(params, cfg, tokens, remat=False)
    return logits_fn(params, cfg, h[0])


def reference_logits(params, cfg: ModelConfig,
                     req: EngineRequest) -> np.ndarray:
    """model_forward over the request's tokens: the logits at every
    position where the engine picked a token, one row per pick."""
    toks = jax.device_put(np.asarray(req.tokens[:-1], np.int32)[None],
                          jax.tree.leaves(params)[0].device)
    return np.asarray(_forward_logits(params, cfg, toks))[req.prompt_len - 1:]


def _requests(size: Size, seed: int) -> List[EngineRequest]:
    return launcher.make_requests(size.cfg, N_REQUESTS, size.prompt_lens,
                                  MAX_NEW, seed, keep_logits=True)


def _serve(size: Size, devices, seed: int):
    """Serve the smoke's requests on one engine per device: (result,
    engines).  Engines hold their device's memory until dropped."""
    engines = launcher.build_engines(size.cfg, devices,
                                     max_batch=size.max_batch,
                                     max_len=size.max_len, seed=seed)
    res = launcher.serve(engines, _requests(size, seed))
    _check_complete(res)
    return res, engines


def _check_complete(res: launcher.ServeResult):
    assert len(res.completed) == N_REQUESTS, \
        f"{len(res.completed)} of {N_REQUESTS} requests completed"
    for r in res.completed:
        assert len(r.generated) == MAX_NEW == len(r.logits), \
            (r.rid, len(r.generated), len(r.logits))


def one_chip_phase(size: Size, device, seed: int) -> Dict:
    """Serve on one engine; check one request of every prompt length
    against model_forward over the same tokens."""
    res, engines = _serve(size, [device], seed)
    by_rid = {r.rid: r for r in res.completed}
    errs = {rid: logit_error(np.stack(by_rid[rid].logits),
                             reference_logits(engines[0].params, size.cfg,
                                              by_rid[rid]))
            for rid in range(len(size.prompt_lens))}
    return {"result": res, "errors": errs,
            "weights": engines[0].weight_bytes}


def replicas_phase(size: Size, devices, seed: int) -> Dict:
    """Serve on one engine per device behind the router, then the same
    requests on one engine, and compare them request by request."""
    many = _serve(size, devices, seed)[0]
    one = _serve(size, devices[:1], seed)[0]
    ref = {r.rid: r for r in one.completed}
    errs, agree = {}, {}
    for r in many.completed:
        a, b = ref[r.rid].generated, r.generated
        same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                    len(a))
        agree[r.rid] = same
        # rows up to the first differing pick saw the same prefix
        rows = min(same + 1, len(a))
        errs[r.rid] = logit_error(np.stack(r.logits[:rows]),
                                  np.stack(ref[r.rid].logits[:rows]))
    return {"result": many, "errors": errs, "agree": agree}


def _peak(device) -> str:
    stats = device.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', 'not reported')}"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--replicas", type=int, default=0,
                    help="run only the replica phase on this many chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend is "
                 f"{devices[0].platform!r}); this smoke runs only on a TPU")
    cache_dir = setup_compile_cache()
    log = CompileLog()
    print(f"device: {devices[0].device_kind} x {len(devices)} "
          f"({devices[0].platform})")
    print(f"compile cache: {cache_dir}")

    size = chip_size()
    cfg, full = size.cfg, get_config(ARCH)
    cut = launcher.DEPTH_CUTS[ARCH]
    print(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}"
          f"x{cfg.head_dim} kv_heads={cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size}, layers {cfg.num_layers} "
          f"of {full.num_layers}: {cut.why}")
    print(f"workload: {N_REQUESTS} requests, prompts {size.prompt_lens} "
          f"tokens, {MAX_NEW} new tokens each, max_batch={size.max_batch} "
          f"max_len={size.max_len}, seed={args.seed}")

    if args.replicas:
        assert args.replicas <= len(devices), \
            f"--replicas {args.replicas} with {len(devices)} devices"
        devs = devices[:args.replicas]
        out = replicas_phase(size, devs, args.seed)
        n_same = sum(v == MAX_NEW for v in out["agree"].values())
        print(f"tokens agreeing with one engine: {n_same} of {N_REQUESTS} "
              f"requests identical, first difference at "
              f"{sorted(out['agree'].items())}")
        against = "one engine"
    else:
        devs = devices[:1]
        out = one_chip_phase(size, devs[0], args.seed)
        print(f"weight bytes held by dtype: {out['weights']}")
        against = "model_forward"
    res = out["result"]
    worst = max(out["errors"].values())
    print(f"logit error against {against} per request: {out['errors']}")
    print(f"largest logit error against {against}: {worst!r} "
          f"(tolerance {LOGIT_TOL})")
    served = [list(res.served_by.values()).count(i)
              for i in range(len(devs))]
    for i, d in enumerate(devs):
        print(f"device {i}: served {served[i]} requests, decode iteration "
              f"EMA {res.est.snapshot(i).d * 1e3!r} ms, "
              f"peak_bytes_in_use={_peak(d)}")
    print(f"served {len(res.completed)} of {N_REQUESTS} requests, "
          f"{res.tokens} tokens in {res.seconds!r} s wall, compiles "
          f"included (single bring-up run)")
    print(log.line())
    assert worst <= LOGIT_TOL, f"logit error {worst} > {LOGIT_TOL}"
    assert min(served) >= 1, f"a device served nothing: {served}"
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
