"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the counted requests that finished (drawn from the seed, the longest one
always in it, and one from every engine) is run through the plain
float32 reference: the prompt followed by the tokens the engine served.
At every position where the engine picked a token, the gap is how far
the reference's logit of that token lies below the reference's best
logit.  Greedy decoding through a correct program picks the best token
up to the rounding of its own arithmetic, so the gap is small; a wrong
cache slot, position, state or token makes it large.

The numbers compared, each against its limit:

- ``gap_mean``: the mean gap over the sample's served positions (logit
  units), against the limit in the cell's file.  The widest gap is
  printed but not compared: the control's widest gap is not three times
  the program's (see PERF.md);
- ``wrong_length``: finished counted requests whose output is not the
  length asked for (limit 0);
- ``out_of_vocab``: served tokens outside the vocabulary (limit 0).

The configuration states the precision the program is held to
(``dtype``): bfloat16, the published one.  The program keeps float32
weights and state, but multiplies at default precision, which on a TPU
rounds every matmul operand to bfloat16: its products are bfloat16's.
The control (``gaps(..., controls=("int8",))``) puts the reference itself
in the program's place, one precision below the stated one: bfloat16
activations on int8 weights (one scale per output channel).  At each
position of the same prompts and served tokens it reads the float32
reference's gap of the token that the control puts first, and goes
through the same limits (``harness.decide``).  ``"bf16"`` reads the
reference computed wholly in bfloat16 (weights, activations and
residual), a program within the stated precision.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(steps, seed: int, target_tokens: int, max_requests: int,
           n_engines: int) -> list:
    """Finished counted steps to check: the longest, then one from every
    engine, then others in the seed's order, until ``target_tokens``
    served tokens or ``max_requests`` requests."""
    done = [s for s in steps if s.counted and s.finish is not None]
    if not done:
        return []
    rng = np.random.default_rng([seed % 2 ** 64, 4])
    rest = [done[i] for i in rng.permutation(len(done))]
    picked = [max(done, key=lambda s: (s.prompt_len + s.out, s.out))]
    for e in range(n_engines):
        if not any(s.engine == e for s in picked):
            picked += [s for s in rest if s.engine == e][:1]
    for s in rest:
        if (len(picked) >= max_requests
                or sum(s.out for s in picked) >= target_tokens):
            break
        if all(s is not p for p in picked):
            picked.append(s)
    return picked


def _pad_to(n: int, multiple: int = 256) -> int:
    return -(-n // multiple) * multiple


def _batches(seqs: Sequence[np.ndarray], batch: int, vocab: int):
    """Rows of ``batch`` sequences padded to one length; tokens outside
    the vocabulary read as 0 (they are counted apart)."""
    L = _pad_to(max(len(s) for s in seqs))
    for i in range(0, len(seqs), batch):
        chunk = seqs[i:i + batch]
        arr = np.zeros((batch, L), np.int32)
        for j, s in enumerate(chunk):
            arr[j, :len(s)] = np.where(s < vocab, s, 0)
        yield i, len(chunk), arr


@functools.lru_cache(maxsize=None)
def _gap_fn(ref, spec_items):
    spec = dict(spec_items)

    def fn(params, toks, picks):
        lg = ref.logits(params, spec, toks)               # [n, L, V] f32
        best = lg.max(-1)
        nxt = jnp.roll(toks, -1, axis=1)
        served = jnp.take_along_axis(lg, nxt[..., None], -1)[..., 0]
        ctrl = jnp.take_along_axis(lg, picks[..., None], -1)[..., 0]
        return best - served, best - ctrl
    return jax.jit(fn)


def int8_weights(params):
    """Every matrix rounded to int8 with one symmetric scale per output
    channel, and back to bfloat16: what serving int8 weights does to
    them.  Layer matrices are stacked ``[layers, in, out]`` and multiply
    as ``x @ w``; the embedding's rows are the output channels of the
    tied logits.  Gains, biases and per-head scalars stay as they are."""
    def q(path, w):
        embed = path[-1].key == "embed"
        if w.ndim < 3 and not embed:      # gains, biases, per-head scalars
            return w
        s = jnp.max(jnp.abs(w), axis=-1 if embed else -2,
                    keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return (jnp.clip(jnp.round(w / s), -127, 127) * s).astype(
            jnp.bfloat16)
    return jax.tree_util.tree_map_with_path(q, params)


CONTROLS = {"int8": int8_weights, "bf16": lambda params: params}


@functools.lru_cache(maxsize=None)
def _pick_fn(ref, spec_items, mode):
    spec = dict(spec_items)
    return jax.jit(lambda params, toks: jnp.argmax(ref.logits(
        CONTROLS[mode](params), spec, toks, jnp.bfloat16), -1).astype(
            jnp.int32))


def _freeze(spec: Dict) -> Tuple:
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, str))))


def gaps(ref, spec: Dict, params, seqs: Sequence[np.ndarray],
         starts: Sequence[int], controls: Sequence[str] = ()
         ) -> Tuple[List[np.ndarray], Dict[str, List[np.ndarray]]]:
    """Per sequence, the reference's gap of each served token at
    positions ``start - 1 .. len - 2`` (the positions that picked a
    token), and per control of ``controls`` the gap of the token that
    the control puts first at the same positions."""
    frozen = _freeze(spec)
    V = spec["vocab_size"]
    served = [None] * len(seqs)
    ctrl = {m: [None] * len(seqs) for m in controls}
    for i, n, toks in _batches(seqs, ref.BATCH, V):
        rows = {}
        for m in controls or (None,):
            picks = (_pick_fn(ref, frozen, m)(params, toks) if m
                     else jnp.zeros_like(toks))
            rows[m] = [np.asarray(a) for a in _gap_fn(ref, frozen)(
                params, toks, picks)]
        g = next(iter(rows.values()))[0]
        for j in range(n):
            lo, hi = starts[i + j] - 1, len(seqs[i + j]) - 1
            ok = seqs[i + j][lo + 1:hi + 1] < V
            served[i + j] = g[j, lo:hi][ok]
            for m in controls:
                ctrl[m][i + j] = rows[m][1][j, lo:hi]
    return served, ctrl


def readings(gap_rows: Sequence[np.ndarray]) -> Dict[str, float]:
    allg = np.concatenate(gap_rows) if gap_rows else np.zeros(0)
    if not allg.size:
        return {"gap_max": float("nan"), "gap_mean": float("nan")}
    return {"gap_max": float(allg.max()), "gap_mean": float(allg.mean())}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each number beside its limit; correct when every one is within
    (a number that could not be read, NaN, is not)."""
    table = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return bool(ok), table
