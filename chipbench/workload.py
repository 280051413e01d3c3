"""The traffic generator: turns a traffic mix's data file into the step
requests of one run.

Agentic workflows (DAGs of step requests) arrive open-loop.  The shapes,
length statistics and role scalings follow the paper's workflow mix
(tool chains, reflection loops, fan-out/join; BIRD SQL, LiveCodeBench and
SWE-bench length families).  A step is due when its workflow arrives (a
root) or when its last parent finishes (a child); that is decided by the
serve loop, not here.

A prompt is its workflow's system prompt (one of ``prefix_groups``),
then the context carried from its parents (each parent's prompt after
the system prompt, and the tokens the parent actually served), then the
step's own new tokens; where that is longer than the prompt's length, the
oldest carried tokens go (truncation on the left), so every step adds
tokens of its own.  A child's prompt is therefore built by the serve
loop when the child is due (:func:`build_prompt`); its length is fixed
here.

Every seed gets the same workflows at the same times, drawn once from
the mix's ``pool_seed``; the run's seed draws the token ids (and the
harness the weights).  The work of a run and its timing do not change
from seed to seed: with only a few dozen workflows in a window, a new
order of the same workflows moved tails and rates by 10-80% from seed to
seed, against under 1% between two runs of one seed.  The window
holds exactly ``round(rate * seconds)`` arrivals: their times are the
normalised cumulative sums of exponential gaps, which is a Poisson
process conditioned on that count.  A lead-in of ``lead_s`` seconds at
the same rate fills the engines before the window opens, so the window
sees the load and not the ramp from an empty system; arrivals go on
after the window (to keep the load on while counted steps drain).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Step:
    """One step request of a workflow, and what the serve loop saw of it."""
    wid: int
    idx: int
    parents: Tuple[int, ...]
    plen: int                     # prompt tokens
    out: int                      # output tokens asked for
    prefix: np.ndarray            # the workflow's system prompt (int32)
    own: np.ndarray               # the step's new tokens (int32)
    # filled by the serve loop
    prompt: Optional[np.ndarray] = None     # see build_prompt
    due: Optional[float] = None
    submitted: Optional[float] = None
    engine: int = -1
    token_times: List[float] = dataclasses.field(default_factory=list)
    finish: Optional[float] = None
    counted: bool = False
    deadline_s: float = 0.0       # relative to ``due``
    req: object = None            # the engine's request (tokens served)

    @property
    def prompt_len(self) -> int:
        return self.plen


@dataclasses.dataclass
class Workflow:
    wid: int
    kind: str
    arrival: float                # seconds after the window opens
    steps: List[Step]


@dataclasses.dataclass(frozen=True)
class Shape:
    """One workflow's plan before tokens: per step (family, role,
    parents, own input length, context length before bucketing, output
    length)."""
    kind: str
    steps: Tuple[Tuple[str, str, Tuple[int, ...], int, int, int], ...]


def max_prompt(mix: Dict, max_len: int) -> int:
    """The longest prompt bucket that leaves room for the output cap:
    prompt + out_cap <= max_len - 1."""
    b = mix["prompt_bucket"]
    return (max_len - 1 - mix["out_cap"]) // b * b


def min_prompt(mix: Dict) -> int:
    """The shortest prompt bucket that holds the system prompt and at
    least one token of the step's own."""
    b = mix["prompt_bucket"]
    return (mix["prefix_tokens"] // b + 1) * b


def bucket(n: int, mix: Dict, max_len: int) -> int:
    b = mix["prompt_bucket"]
    return int(min(max(-(-n // b) * b, min_prompt(mix)),
                   max_prompt(mix, max_len)))


def prompt_buckets(mix: Dict, max_len: int) -> List[int]:
    """Every prompt length the mix can send to an engine of ``max_len``."""
    return list(range(min_prompt(mix), max_prompt(mix, max_len) + 1,
                      mix["prompt_bucket"]))


def _sample_len(rng, fam: Dict) -> Tuple[int, float]:
    complexity = int(rng.integers(0, 8))
    input_len = max(int(rng.normal(fam["in_mean"], fam["in_std"])), 32)
    out = rng.lognormal(math.log(fam["out_median"]), fam["out_sigma"])
    out += fam["complexity_gain"] * complexity * rng.uniform(0.6, 1.4)
    if fam.get("bimodal_frac") and rng.random() < fam["bimodal_frac"]:
        out *= fam["bimodal_mult"]
    return input_len, float(np.clip(out, 8, 8192))


def _plan(rng, shape: str, n_range: Sequence[int], families: Sequence[str]
          ) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """(family, role, parents) per step for one template."""
    n = int(rng.integers(n_range[0], n_range[1] + 1))
    if shape == "single":
        return [(families[rng.integers(len(families))], "chat", ())]
    if shape == "chain":
        return [(families[rng.integers(len(families))], "tool",
                 () if i == 0 else (i - 1,)) for i in range(n)]
    if shape == "loop":
        plan = [("code", "draft", ())]
        for _ in range(n):
            plan.append(("swe", "critique", (len(plan) - 1,)))
            plan.append(("code", "revise", (len(plan) - 1,)))
        return plan
    if shape == "fanout":
        plan = [("code", "plan", ())]
        plan += [(("sql", "swe")[rng.integers(2)], "tool", (0,))
                 for _ in range(n)]
        plan.append(("code", "synth", tuple(range(1, n + 1))))
        return plan
    raise ValueError(f"unknown workflow shape {shape!r}")


def draw_shape(rng, mix: Dict) -> Shape:
    names = sorted(mix["templates"])
    w = np.array([mix["templates"][k]["weight"] for k in names], float)
    kind = names[int(rng.choice(len(names), p=w / w.sum()))]
    t = mix["templates"][kind]
    plan = _plan(rng, t["shape"], t["n"], sorted(mix["families"]))
    ctx_lens, outs, steps = [], [], []
    for family, role, parents in plan:
        input_len, out = _sample_len(rng, mix["families"][family])
        out = int(np.clip(out * mix["role_out_scale"][role], 8, 8192))
        # context carried from the parents: their whole context plus the
        # output each one appended
        ctx = input_len + sum(ctx_lens[p] + outs[p] for p in parents)
        ctx_lens.append(ctx)
        outs.append(out)
        steps.append((family, role, tuple(parents), input_len, ctx,
                      int(min(max(out, mix["out_min"]), mix["out_cap"]))))
    return Shape(kind, tuple(steps))


def pool(mix: Dict, n: int) -> List[Shape]:
    """The mix's fixed set of ``n`` workflow shapes (from ``pool_seed``)."""
    rng = np.random.default_rng(mix["pool_seed"])
    return [draw_shape(rng, mix) for _ in range(n)]


def _segment(rng, n: int, length: float) -> np.ndarray:
    """``n`` arrival offsets in [0, length): normalised cumulative sums of
    exponential gaps."""
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * length


def counts(mix: Dict, rate: float, seconds: float) -> Tuple[int, int, int]:
    """Workflows arriving in the lead-in, the window and the tail."""
    return (int(round(rate * mix["lead_s"])),
            max(int(round(rate * seconds)), 1),
            int(math.ceil(rate * mix["tail_s"])))


def arrival_times(mix: Dict, rate: float, seconds: float) -> np.ndarray:
    """Arrival offsets from the window's opening: the lead-in's in
    [-lead_s, 0), exactly ``round(rate * seconds)`` in the window, then
    the tail's in [seconds, seconds + tail_s), all from ``pool_seed``."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    rng = np.random.default_rng([mix["pool_seed"], 1])
    n_lead, n_win, n_tail = counts(mix, rate, seconds)
    return np.concatenate([
        _segment(rng, n_lead, mix["lead_s"]) - mix["lead_s"],
        _segment(rng, n_win, seconds),
        seconds + _segment(rng, n_tail, mix["tail_s"])])


def make_workflows(mix: Dict, rate: float, seconds: float, max_len: int,
                   vocab: int, seed: int) -> List[Workflow]:
    """Every workflow of one run: shapes from the mix's fixed pool and
    arrivals as in :func:`arrival_times`, token ids from the seed.  Each
    step gets its prompt length, its workflow's system prompt and enough
    new tokens of its own to fill its prompt with what its parents carry
    (at least its own input length, where there is room)."""
    arr = arrival_times(mix, rate, seconds)
    shapes = pool(mix, len(arr))
    toks = np.random.default_rng([seed % 2 ** 64, 3])
    P = mix["prefix_tokens"]
    prefixes = toks.integers(0, vocab, (mix["prefix_groups"], P), np.int32)
    wfs = []
    for wid, (a, shape) in enumerate(zip(arr, shapes)):
        group = int(toks.integers(mix["prefix_groups"]))
        steps: List[Step] = []
        for i, (_, _, parents, input_len, ctx_len, out) in enumerate(
                shape.steps):
            plen = bucket(ctx_len, mix, max_len)
            carried = sum(steps[p].plen - P + steps[p].out for p in parents)
            n_own = min(max(input_len, plen - P - carried), plen - P)
            steps.append(Step(
                wid=wid, idx=i, parents=parents, plen=plen, out=out,
                prefix=prefixes[group],
                own=toks.integers(0, vocab, n_own, np.int32)))
        wfs.append(Workflow(wid, shape.kind, float(a), steps))
    return wfs


def build_prompt(step: Step, parents: Sequence[np.ndarray]) -> np.ndarray:
    """The prompt of ``step`` once its parents have served: the system
    prompt, then each parent's tokens after it (prompt and served output,
    ``parents`` in the step's order of parents), then the step's own
    tokens, cut on the left to the step's length after the system
    prompt.  Sets and returns ``step.prompt``."""
    P = step.prefix.shape[0]
    body = np.concatenate([np.asarray(t, np.int32)[P:] for t in parents]
                          + [step.own])
    step.prompt = np.concatenate([step.prefix,
                                  body[body.shape[0] - (step.plen - P):]])
    return step.prompt


def clipped_share(mix: Dict, rate: float, seconds: float, max_len: int
                  ) -> Tuple[float, float]:
    """Of the steps of one run's schedule: the share whose context is cut
    to the longest prompt bucket, and the share whose prompt leaves out
    some of its context (of what its parents carry, or of its own
    input)."""
    wfs = make_workflows(mix, rate, seconds, max_len, 2, 0)
    top, P = max_prompt(mix, max_len), mix["prefix_tokens"]
    n = capped = cut = 0
    for wf, shape in zip(wfs, pool(mix, len(wfs))):
        for s, st in zip(wf.steps, shape.steps):
            carried = sum(wf.steps[p].plen - P + wf.steps[p].out
                          for p in s.parents)
            n += 1
            capped += st[4] > top
            cut += carried + st[3] > s.plen - P
    return capped / n, cut / n


def deadline(step: Step, solo: Dict, slo_scale: float) -> float:
    """``slo_scale`` times the step's solo latency on the cell's engine:
    solo TTFT for its prompt plus its output at the solo TPOT."""
    ttft = solo["ttft_base_s"] + solo["ttft_per_token_s"] * step.prompt_len
    return slo_scale * (ttft + step.out * solo["tpot_s"])
