"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into a :class:`Trace`: per
device, the device operations (the ``XLA Ops`` line) and the programs
(the ``XLA Modules`` line) as (name, start, end) in nanoseconds, the
harness's host spans (``jax.profiler.TraceAnnotation``), and whatever the
profiler says it dropped (a stat or line whose name speaks of dropped
trace buffers).  Everything
else works on a :class:`Trace`, so it is checked on small made-up ones.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

HOST_SPANS = ("anchor", "client", "route", "engine.step", "drain_events",
              "wait")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]           # device plane -> operations
    modules: Dict[str, List[Interval]]       # device plane -> programs
    spans: List[Interval]                    # host spans of the harness
    offset_ns: float = 0.0                   # trace time at host time 0
    dropped: List[str] = dataclasses.field(default_factory=list)

    def anchor(self, t_anchor: float):
        """Put the host clock on the trace's: ``t_anchor`` is the host time
        (``time.perf_counter``) at which the one ``anchor`` span opened."""
        w = [s for s in self.spans if s[0] == "anchor"]
        if len(w) != 1:
            raise ValueError(f"{len(w)} anchor spans in the trace")
        self.offset_ns = w[0][1] - t_anchor * 1e9

    def at(self, t: float) -> float:
        """Host time ``t`` on the trace's clock (ns)."""
        return t * 1e9 + self.offset_ns


def op_name(text: str) -> str:
    """A device operation's short name from its HLO text
    (``%fusion.69 = f32[16,122880]{...} fusion(...)`` gives
    ``%fusion.69 fusion f32[16,122880]``)."""
    lhs, _, rhs = text.partition(" = ")
    m = re.search(r"\s([a-z][\w-]*)\(", " " + rhs)
    shape = rhs.split("{", 1)[0] if not rhs.startswith("(") else "(tuple)"
    return f"{lhs} {m.group(1) if m else '?'} {shape}".strip()


def load(log_dir: str) -> Trace:
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{len(paths)} xplane files under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    ops, modules, spans, dropped = {}, {}, [], []
    for plane in data.planes:
        dropped += [f"{plane.name} {k}={v}" for k, v in plane.stats
                    if "drop" in k.lower()]
        if re.match(r"/device:TPU:\d+$", plane.name):
            for line in plane.lines:
                if "drop" in line.name.lower():
                    dropped.append(f"{plane.name} line {line.name!r}: "
                                   f"{sum(1 for _ in line.events)} events")
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events)
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in HOST_SPANS)
    return Trace(ops, modules, spans, dropped=dropped)


def clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in iv if b > lo and a < hi]


def union(iv: Sequence[Interval]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, a, b in sorted(iv, key=lambda x: x[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(iv: Sequence[Interval]) -> List[Tuple[str, float]]:
    """(name, own duration) of each interval: its duration less that of
    the intervals nested in it (the body of a ``while`` runs inside the
    ``while`` operation)."""
    order = sorted(range(len(iv)), key=lambda i: (iv[i][1], -iv[i][2]))
    own = [b - a for _, a, b in iv]
    stack: List[int] = []
    for i in order:
        while stack and iv[stack[-1]][2] <= iv[i][1]:
            stack.pop()
        if stack:
            own[stack[-1]] -= iv[i][2] - iv[i][1]
        stack.append(i)
    return [(iv[i][0], own[i]) for i in range(len(iv))]


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` operation names with the most device seconds of their
    own in the window, averaged over devices."""
    tot: Dict[str, float] = {}
    for v in tr.ops.values():
        for n, s in self_times(clip(v, lo, hi)):
            tot[n] = tot.get(n, 0.0) + s
    n_dev = max(len(tr.ops), 1)
    return sorted(((n, t * 1e-9 / n_dev) for n, t in tot.items()),
                  key=lambda x: -x[1])[:k]


def _host_at(spans: Sequence[Interval], starts: Sequence[float],
             t: float) -> str:
    """The innermost harness span around ``t``; ``spans`` (the anchor
    left out) are sorted by start, and nest at most a few deep."""
    i = bisect.bisect_right(starts, t)
    around = [s for s in spans[max(i - 4, 0):i] if s[2] >= t]
    if not around:
        return "host (no span)"
    return min(around, key=lambda s: s[2] - s[1])[0]


def idle_gaps(tr: Trace, lo: float, hi: float, k: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``k`` longest stretches of the window in which the first
    device ran nothing, each named by what the host was doing at its
    middle."""
    if not tr.ops:
        return []
    dev = sorted(tr.ops)[0]
    busy = union(clip(tr.ops[dev], lo, hi))
    edges = [lo] + [x for a, b in busy for x in (a, b)] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = sorted((s for s in tr.spans if s[0] != "anchor"),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    return [(_host_at(spans, starts, (a + b) / 2), (b - a) * 1e-9)
            for a, b in gaps[:k]]


def idle_by_host(tr: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds of the first device in the window, summed by what the
    host was doing."""
    out: Dict[str, float] = {}
    for name, s in idle_gaps(tr, lo, hi, k=10 ** 9):
        out[name] = out.get(name, 0.0) + s
    return out
