"""The model work of the window's decode and prefill steps, and the
device seconds their programs took in the trace: what the roofline and
utilisation readers divide.

A program's device time is attributed by where its execution ends: every
program that ends inside one engine step (the harness's span around
``InferenceEngine.step``, moved onto the trace's clock) belongs to that
step.  A step runs its admissions first (a prefill program and a cache
splice per admitted request) and its decode last, so the last program of
a step that is not a splice is the decode; the rest are prefill work.
Splices are known by the name ``jit_splice_cache``; the decode and
prefill programs have no name of their own in the trace (they are jitted
``functools.partial`` objects, named ``jit__unknown``).

The profiler can drop stretches of its trace.  A step of the window that
did device work (the engine reported a decode or a prefill for it) but
has no program ending inside its span in the trace is lost: its work is
not counted against the trace's device time, and its span is taken out
of the traced window and of the device's busy time (:func:`coverage`).
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Set, Tuple

from chipbench import trace as tracelib

SPLICE = "jit_splice_cache"


def _least(flops: float, nbytes: float, peak) -> float:
    return max(flops / peak["bf16_flops_s"], nbytes / peak["hbm_bytes_s"])


def lost_steps(run) -> Tuple[Set[Tuple[int, float]],
                             Dict[str, List[Tuple[float, float]]]]:
    """The window's steps (engine, end on the host clock) that did device
    work but whose programs the trace lost, and per device plane their
    spans on the trace's clock, clipped to the window."""
    lo, hi = run.window_ns()
    worked = {(g, t) for g, _, _, _, t in run.rec.events}
    ends = {p: sorted(m[2] for m in run.trace.modules.get(p, []))
            for p in set(run.rec.planes)}
    lost, spans = set(), {}
    for g, a, t in run.rec.step_spans:
        if (g, t) not in worked or not run.rec.in_window(t):
            continue
        plane = run.rec.planes[g]
        a_ns, t_ns = run.trace.at(a), run.trace.at(t)
        i = bisect.bisect_left(ends[plane], a_ns)
        if i == len(ends[plane]) or ends[plane][i] > t_ns:
            lost.add((g, t))
            spans.setdefault(plane, []).append((max(a_ns, lo), t_ns))
    return lost, spans


def coverage(run) -> Tuple[float, float]:
    """(busy seconds, traced seconds) of the window, averaged over the
    device planes in the trace: the window less the spans of the steps
    the trace lost, and the device's busy time in what is left."""
    lo, hi = run.window_ns()
    _, spans = lost_steps(run)
    busy = window = 0.0
    planes = [p for p in sorted(set(run.rec.planes)) if p in run.trace.ops]
    for p in planes:
        gaps = tracelib.union([("", a, b) for a, b in spans.get(p, [])])
        ops = tracelib.union(tracelib.clip(run.trace.ops[p], lo, hi))
        busy += sum(b - a for a, b in ops) - _overlap(ops, gaps)
        window += hi - lo - sum(d - c for c, d in gaps)
    if not planes:
        return 0.0, (hi - lo) * 1e-9
    return busy / len(planes) * 1e-9, window / len(planes) * 1e-9


def _overlap(xs, ys) -> float:
    """Length of the overlap of two sorted lists of disjoint intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        a, b = xs[i]
        c, d = ys[j]
        tot += max(0.0, min(b, d) - max(a, c))
        if b < d:
            i += 1
        else:
            j += 1
    return tot


def _kept(run):
    """A test of whether a step (engine, end) counts: it ends inside the
    window, and its programs are in the trace where there is one."""
    lost = lost_steps(run)[0] if run.trace is not None else set()
    return lambda g, t: run.rec.in_window(t) and (g, t) not in lost


def decode(run) -> Tuple[float, float]:
    """(model operations, least seconds) of the decode steps whose tokens
    reached the host inside the window (and whose programs the trace
    holds)."""
    flops = least = 0.0
    kept = _kept(run)
    for g, t, ctxs in run.rec.decodes:
        if kept(g, t):
            f, b = run.cost.decode(run.cell.spec, ctxs)
            flops += f
            least += _least(f, b, run.peak)
    return flops, least


def prefill(run) -> Tuple[float, float]:
    flops = least = 0.0
    kept = _kept(run)
    for g, kind, size, _, t in run.rec.events:
        if kind == "prefill" and kept(g, t):
            f, b = run.cost.prefill(run.cell.spec, size)
            flops += f
            least += _least(f, b, run.peak)
    return flops, least


def device_s(run) -> Optional[Dict[str, float]]:
    """Device seconds of decode and of prefill programs in the traced
    window, summed over the chips; None without a trace."""
    if run.trace is None:
        return None
    lo, hi = run.window_ns()
    tot = {"decode": 0.0, "prefill": 0.0}
    for gid, plane in enumerate(run.rec.planes):
        mods = sorted(tracelib.clip(run.trace.modules.get(plane, []), lo, hi),
                      key=lambda m: m[2])
        j = 0
        for g, a, b in run.rec.step_spans:
            if g != gid:
                continue
            a, b = run.trace.at(a), run.trace.at(b)
            inside = []
            while j < len(mods) and mods[j][2] <= b:
                if mods[j][2] >= a:
                    inside.append(mods[j])
                j += 1
            last = [m for m in inside if not m[0].startswith(SPLICE)][-1:]
            for m in inside:
                tot["decode" if m in last else "prefill"] += m[2] - m[1]
    return {k: v * 1e-9 for k, v in tot.items()}


def share(num: float, den: Optional[float]) -> Optional[float]:
    return None if not den or not num else 100.0 * num / den
