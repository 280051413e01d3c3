"""End-to-end serving driver (deliverable b): real JAX inference engines
(the same model code the benchmark serves on the TPU) serving batched requests
behind an EMA-monitored proxy, including a token-ID migration of an
in-flight request between engines — the paper's mechanism, live.

  PYTHONPATH=src python examples/serve_engine.py
"""
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core.estimator import EMAEstimator
from repro.engine.engine import EngineRequest, InferenceEngine


def main():
    cfg = reduce_config(get_config("llama3.1-8b"))
    print(f"serving {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")
    # replicas of one model: one seed for all
    engines = [InferenceEngine(cfg, max_batch=4, max_len=96,
                               prefill_chunk=16)
               for _ in range(2)]
    est = EMAEstimator()
    rng = np.random.default_rng(0)

    reqs = []
    for rid in range(10):
        prompt = list(map(int, rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(8, 20)))))
        r = EngineRequest(rid=rid, tokens=prompt, prompt_len=len(prompt),
                          max_new_tokens=10)
        reqs.append(r)
        engines[rid % 2].submit(r)

    # run a few iterations, then migrate one in-flight request by token IDs
    for _ in range(4):
        for e in engines:
            e.step()
    snap = engines[0].checkpoint_request(reqs[0].rid)
    if snap is not None:
        print(f"migrating request {snap.rid} with "
              f"{len(snap.generated)} generated tokens "
              f"(token-ID transfer, Sec. 3.4)")
        engines[1].submit(snap)     # re-prefills prompt+generated at target

    while sum(len(e.completed) for e in engines) < len(reqs):
        for gid, e in enumerate(engines):
            e.step()
            for kind, size, dt in e.drain_events():
                (est.observe_decode_iter if kind == "decode"
                 else est.observe_prefill)(gid, *((dt,) if kind == "decode"
                                                  else (size, dt)))

    for gid, e in enumerate(engines):
        d = est.snapshot(gid).d * 1e3
        print(f"engine{gid}: completed={len(e.completed)} "
              f"ema_tpot={d:.1f}ms")
    migrated = [r for e in engines for r in e.completed
                if r.rid == reqs[0].rid]
    print(f"migrated request finished with "
          f"{len(migrated[0].generated)} tokens" if migrated else
          "migrated request still running")


if __name__ == "__main__":
    main()
