"""The harness: resolves a cell by name, builds the engines, drives the
open-loop window, reads the metrics and decides ``correct``.

Everything that belongs to one configuration, traffic mix, cell or
metric is found by name:

- ``BENCHMARK.json``: the cell (configuration, traffic, chips) and the
  metrics it reports;
- the configuration's file (``configs[].file``), whose ``arch`` names
  ``chipbench/reference/<arch>.py`` (plain reference and weight laws),
  ``chipbench/cost/<arch>.py`` (operations and bytes) and
  ``chipbench/program/<arch>.py`` (the program's model configuration);
- ``chipbench/traffic/<traffic>.json``: the traffic mix;
- ``chipbench/cells/<cell>.json``: the cell's rate, the solo latencies
  its deadlines come from, and the limits of its check;
- ``chipbench/metrics/<metric>.py``: one reader per metric.

The window drives the program's own serving loop (``repro.launch.serve``:
``route`` with an ``EMAEstimator``, ``InferenceEngine.submit``/``step``/
``drain_events``), with arrivals added: the program has no open-loop
entry of its own.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import pathlib
import shutil
import tempfile
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from chipbench import check, stats, weights, work
from chipbench import trace as tracelib
from chipbench import workload

ROOT = pathlib.Path(__file__).resolve().parents[1]
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: Dict                 # the configuration's file
    mix: Dict                  # the traffic mix
    params: Dict               # the cell's own file
    end_to_end: List[Dict]     # BENCHMARK.json metric entries it reports
    per_layer: List[Dict]

    @property
    def arch(self) -> str:
        return self.spec["arch"]

    def module(self, kind: str):
        return importlib.import_module(f"chipbench.{kind}.{self.arch}")


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = [w for w in bench["workloads"] if w["name"] == name]
    if len(entry) != 1:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    entry = entry[0]
    (conf,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    base = root / "chipbench"
    return Cell(
        name=name, chips=entry["chips"],
        spec=json.loads((root / conf["file"]).read_text()),
        mix=json.loads((base / "traffic" / f"{entry['traffic']}.json")
                       .read_text()),
        params=json.loads((base / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def read_metric(name: str, run) -> Optional[float]:
    mod = importlib.import_module(f"chipbench.metrics.{name}")
    return mod.read(run)


# ---------------------------------------------------------------------------
# Host spans and compile counting
# ---------------------------------------------------------------------------

def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileLog:
    """Programs compiled (or loaded from the persistent cache) and the
    seconds spent on it, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.n, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------

def build_engines(cell: Cell, cfg, devices, seed: int):
    """One engine per device, each with the weights made from ``seed`` on
    its own device (replicas of one model), in the type the
    configuration says the program stores them in."""
    import jax
    import jax.numpy as jnp
    from repro.engine.engine import InferenceEngine
    from repro.models import init_params

    ref = cell.module("reference")
    dtype = jnp.dtype(cell.spec["storage"])
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, dtype=dtype),
                            jax.random.PRNGKey(0))
    serve = cell.spec["serve"]
    return [InferenceEngine(
        cfg, weights.generate(ref.draw, cell.spec, shapes, seed, d),
        max_batch=serve["max_batch"], max_len=serve["max_len"], device=d)
        for d in devices], shapes


def warm_up(engines, prompt_lens, vocab: int):
    """Serve one request of every prompt length the run will send, on
    every engine: every program of the window is compiled (or loaded from
    the cache) before it opens."""
    from repro.engine.engine import EngineRequest
    rng = np.random.default_rng(0)
    for eng in engines:
        for i, n in enumerate(sorted(prompt_lens)):
            eng.submit(EngineRequest(
                rid=-1 - i, tokens=[int(t) for t in rng.integers(0, vocab, n)],
                prompt_len=n, max_new_tokens=2))
        eng.run_until_drained()
        eng.completed.clear()
        eng.drain_events()


# ---------------------------------------------------------------------------
# The open-loop window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """What the serve loop saw, on the host clock.  ``backlog`` samples
    the steps queued or running, every half second."""
    steps: List[workload.Step]
    t_load: float = 0.0               # the load starts (lead-in)
    t0: float = 0.0                   # the window opens
    seconds: float = 0.0
    end: float = 0.0                  # the loop stopped
    events: List[tuple] = dataclasses.field(default_factory=list)
    decodes: List[tuple] = dataclasses.field(default_factory=list)
    lateness: List[float] = dataclasses.field(default_factory=list)
    routed: List[int] = dataclasses.field(default_factory=list)
    backlog: List[tuple] = dataclasses.field(default_factory=list)
    # (engine, start, end) of every engine step, and each engine's device
    # plane in the profiler's trace
    step_spans: List[tuple] = dataclasses.field(default_factory=list)
    planes: List[str] = dataclasses.field(default_factory=list)

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t0 + self.seconds


class Tracer:
    """The profiler around the window: started ``lead_s`` before the
    window opens, so that its start-up stalls the lead-in and not the
    window, and stopped at the first turn of the serve loop after the
    window closes.  A shorter trace keeps the profiler's buffers from
    overflowing (they drop whole stretches of device operations when
    they do).  ``t_anchor`` is the host time of the ``anchor`` span."""

    def __init__(self, lead_s: float = 3.0):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.lead_s = lead_s
        self.t_anchor: Optional[float] = None
        self.stopped = False

    def poll(self, now: float, t0: float, seconds: float, clock):
        import jax
        if self.t_anchor is None and now >= t0 - self.lead_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_anchor = clock()
            with span("anchor"):
                pass
        elif (self.t_anchor is not None and not self.stopped
              and now >= t0 + seconds):
            jax.profiler.stop_trace()
            self.stopped = True

    def load(self) -> tracelib.Trace:
        if not self.stopped:
            import jax
            jax.profiler.stop_trace()
            self.stopped = True
        tr = tracelib.load(self.dir)
        tr.anchor(self.t_anchor)
        shutil.rmtree(self.dir, ignore_errors=True)
        return tr


def serve_window(engines, wfs: List[workload.Workflow], seconds: float,
                 deadline_of: Callable[[workload.Step], float],
                 clock=time.perf_counter,
                 tracer: Optional[Tracer] = None) -> Record:
    """Offer ``wfs`` open-loop, starting now with the lead-in (arrivals
    before 0); the window opens at offset 0 and lasts ``seconds``.  Then
    keep the load on until every step due in the window has finished or
    passed its deadline (or the traffic's arrivals run out).  A
    ``tracer`` is polled at every turn of the loop, and then every
    counted step has to finish."""
    from repro.core.estimator import EMAEstimator
    from repro.engine.engine import EngineRequest
    from repro.launch.serve import route

    est = EMAEstimator()
    steps = [s for wf in wfs for s in wf.steps]
    rec = Record(steps=steps, seconds=seconds, routed=[0] * len(engines),
                 planes=[f"/device:{e.device.platform.upper()}:"
                         f"{e.device.id}" for e in engines])
    children = {id(s): [c for c in wf.steps if s.idx in c.parents]
                for wf in wfs for s in wf.steps}
    by_wid = {wf.wid: wf for wf in wfs}
    inflight: List[Dict[int, workload.Step]] = [{} for _ in engines]
    ready: deque = deque()
    roots = sorted(wfs, key=lambda w: w.arrival)
    ri, rid = 0, 0
    rec.t_load = clock()
    t0 = rec.t0 = rec.t_load - min(roots[0].arrival, 0.0)
    last_arrival = t0 + roots[-1].arrival

    def make_due(s: workload.Step, t: float):
        wf = by_wid[s.wid]
        workload.build_prompt(s, [wf.steps[p].req.tokens for p in s.parents])
        s.due, s.counted = t, t0 <= t < t0 + seconds
        s.deadline_s = deadline_of(s)
        ready.append(s)

    while True:
        now = clock()
        if tracer is not None:
            tracer.poll(now, t0, seconds, clock)
        with span("client"):
            while ri < len(roots) and t0 + roots[ri].arrival <= now:
                wf = roots[ri]
                ri += 1
                for s in wf.steps:
                    if not s.parents:
                        make_due(s, t0 + wf.arrival)
            while ready:
                s = ready.popleft()
                with span("route"):
                    gid = route(engines, est)
                s.req = EngineRequest(rid=rid, tokens=s.prompt.tolist(),
                                      prompt_len=s.prompt_len,
                                      max_new_tokens=s.out)
                s.engine, s.submitted = gid, clock()
                if not s.parents:
                    rec.lateness.append(s.submitted - s.due)
                engines[gid].submit(s.req)
                inflight[gid][rid] = s
                rec.routed[gid] += 1
                rid += 1
        worked = False
        for gid, eng in enumerate(engines):
            if not eng.pending:
                continue
            before = {r: len(s.req.tokens)
                      for r, s in inflight[gid].items()}
            a = clock()
            with span("engine.step"):
                eng.step()
            t = clock()
            rec.step_spans.append((gid, a, t))
            worked = True
            ctxs = []
            for r, s in list(inflight[gid].items()):
                new = len(s.req.tokens) - before[r]
                if new:
                    s.token_times.extend([t] * new)
                    ctxs.append(len(s.req.tokens) - 1)
                if s.req.done:
                    s.finish = t
                    del inflight[gid][r]
                    wf = by_wid[s.wid]
                    for c in children[id(s)]:
                        if all(wf.steps[p].finish is not None
                               for p in c.parents):
                            make_due(c, t)
            if ctxs:
                rec.decodes.append((gid, t, ctxs))
            with span("drain_events"):
                for kind, size, dt in eng.drain_events():
                    if kind == "decode":
                        est.observe_decode_iter(gid, dt)
                    else:
                        est.observe_prefill(gid, size, dt)
                    rec.events.append((gid, kind, size, dt, t))
        now = clock()
        if not rec.backlog or now - rec.backlog[-1][0] >= 0.5:
            rec.backlog.append((now, len(ready) + sum(
                e.pending for e in engines)))
        # a traced run waits for every counted step to finish: stopping
        # the profiler stalls the loop past many deadlines
        if now >= t0 + seconds and (now >= last_arrival or all(
                s.finish is not None
                or (tracer is None and now > s.due + s.deadline_s)
                for s in steps if s.counted)):
            break
        if not worked and not ready and ri < len(roots):
            with span("wait"):
                time.sleep(max(0.0, min(t0 + roots[ri].arrival - now,
                                        0.002)))
    rec.end = clock()
    return rec


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    rec: Record
    setup_s: float
    cost: object                      # chipbench.cost.<arch>
    peak: Dict                        # the device's row of peaks.json
    trace: Optional[tracelib.Trace] = None

    def window_ns(self):
        """The window on the trace's clock."""
        r = self.rec
        return self.trace.at(r.t0), self.trace.at(r.t0 + r.seconds)


def device_peaks(kind: str) -> Dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


@dataclasses.dataclass
class Served:
    """One window served, with the program's state already freed."""
    rec: Record
    shapes: object                    # the weights' tree of shapes
    setup_s: float
    compiles_in_window: int
    peak_bytes: List[int]             # peak_bytes_in_use per device
    trace: Optional[tracelib.Trace]


def serve_phase(cell: Cell, seed: int, seconds: float, traced: bool,
                devices, start: float, log=print, faults=None) -> Served:
    """Build the engines from the seed, warm up every shape the run will
    use, serve the window (under the profiler when ``traced``), read the
    peak memory and free the engines.  ``start`` is the process's start
    on the host clock; ``faults`` (tests only) may break the engines'
    timed path after they are built."""
    compiles = CompileLog()
    cfg = cell.module("program").config(cell.spec)
    serve, p = cell.spec["serve"], cell.params
    wfs = workload.make_workflows(cell.mix, p["rate_wps"], seconds,
                                  serve["max_len"], cell.spec["vocab_size"],
                                  seed)
    lens = {s.prompt_len for wf in wfs for s in wf.steps}
    t_engines = time.perf_counter()
    engines, shapes = build_engines(cell, cfg, devices, seed)
    if faults:
        faults(engines)
    t_warm = time.perf_counter()
    warm_up(engines, lens, cell.spec["vocab_size"])
    log(f"warm-up: prompt lengths {sorted(lens)} on {len(engines)} "
        f"engine(s); {compiles.n} programs compiled in "
        f"{compiles.seconds:.3f} s, {compiles.cache_hits} from the cache")
    n_before = compiles.n
    tracer = Tracer() if traced else None
    # set-up ends where the load starts: the lead-in's first arrival
    setup_s = time.perf_counter() - start
    log(f"set-up: {t_engines - start:.3f} s to the engines, engines and "
        f"weights {t_warm - t_engines:.3f} s, warm-up "
        f"{time.perf_counter() - t_warm:.3f} s")
    rec = serve_window(engines, wfs, seconds, lambda s: workload.deadline(
        s, p["solo"], cell.mix["slo_scale"]), tracer=tracer)
    in_window = compiles.n - n_before
    tr = tracer.load() if traced else None
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]
    del engines
    gc.collect()
    return Served(rec, shapes, setup_s, in_window, peak, tr)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             devices, start: float, log=print, faults=None) -> Dict:
    """One run of ``cell``: returns the result line as a dict."""
    import jax

    sv = serve_phase(cell, seed, seconds, traced, devices, start, log,
                     faults)
    rec, tr, dev = sv.rec, sv.trace, devices[0]
    verdict, table = decide(cell, sv.shapes, rec, seed, dev, log)
    counted = [s for s in rec.steps if s.counted]
    failed = sum(s.finish is None for s in counted)

    log(f"compiles inside the window: {sv.compiles_in_window}")
    for d, b in zip(devices, sv.peak_bytes):
        log(f"peak_bytes_in_use {d}: {b}")
    late = rec.lateness or [0.0]
    log(f"generator lateness: max {max(late):.6f} s, mean "
        f"{float(np.mean(late)):.6f} s over {len(rec.lateness)} arrivals")
    tt, gaps = stats.ttfts(rec.steps, rec.end), stats.token_gaps(rec.steps)
    if tt:
        log(stats.tail_line("ttft_p90", tt, 90))
    if gaps:
        log(stats.tail_line("tpot_p95", gaps, 95) + "; gaps p50/p90/p95/"
            "p99 ms " + " ".join(f"{1e3 * stats.percentile(gaps, q):.3f}"
                                 for q in (50, 90, 95, 99)))
    log(f"steps due in the window {len(counted)}, finished "
        f"{len(counted) - failed}, met deadline "
        f"{sum(stats.met_deadline(s) for s in counted)}; routed per engine "
        f"{rec.routed}; window {seconds} s, loop ran "
        f"{rec.end - rec.t0:.3f} s")

    result = {"correct": verdict, "attempted": len(counted),
              "failed": failed}
    metrics = {}
    on_tpu = dev.platform == "tpu"
    run = Run(cell, rec, sv.setup_s, cell.module("cost"),
              device_peaks(dev.device_kind) if on_tpu else {}, tr)
    if on_tpu:
        for m in (cell.per_layer if traced else cell.end_to_end):
            v = read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": max(sv.peak_bytes)}
    if tr is not None:
        lo, hi = run.window_ns()
        busy, window = work.coverage(run)
        result["device"]["busy_s"] = busy
        result["device"]["window_s"] = window
        lost = work.lost_steps(run)[0]
        prog_s = sum(work.device_s(run).values())
        event_s = sum(e[3] for e in rec.events if rec.in_window(e[4]))
        log(f"trace: {len(lost)} of the window's steps lost (their "
            f"programs are not in the trace), traced window {window:.6f} "
            f"of {rec.seconds} s; programs {prog_s:.6f} s of device time "
            f"against {event_s:.6f} s of engine event time; the profiler "
            f"reports dropped: {tr.dropped or 'nothing'}")
        result["breakdown"] = {
            "device_ops": [list(x) for x in tracelib.top_ops(tr, lo, hi)],
            "idle_gaps": [list(x) for x in tracelib.idle_gaps(tr, lo, hi)]}
        idle = tracelib.idle_by_host(tr, lo, hi)
        log("device idle seconds by host activity: "
            + ", ".join(f"{k} {v:.6f}" for k, v in sorted(idle.items())))
    for k, v in table.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    result["check"] = table
    return result


def decide(cell: Cell, shapes, rec: Record, seed: int, device, log,
           controls: Sequence[str] = ()):
    """The comparison with the plain reference (``chipbench.check``) on a
    sample of what the window served; the program's state is freed.
    Returns ``(correct, table)`` for the program, and with ``controls``
    also ``{control: (correct, table)}``: each control's gaps put in the
    place of the program's, under the same limits."""
    lim = cell.params["check"]
    counted = [s for s in rec.steps if s.counted and s.finish is not None]
    V = cell.spec["vocab_size"]
    wrong_len = sum(len(s.req.tokens) - s.prompt_len != s.out
                    for s in counted)
    oov = sum(int(np.sum(np.asarray(s.req.tokens[s.prompt_len:]) >= V))
              for s in counted)
    picked = check.sample(rec.steps, seed, lim["sample_tokens"],
                          lim["sample_max"], len(rec.routed))
    base = {"wrong_length": float(wrong_len), "out_of_vocab": float(oov)}
    rows, ctrl = [], {m: [] for m in controls}
    if picked:
        ref = cell.module("reference")
        params = weights.generate(ref.draw, cell.spec, shapes, seed, device)
        t = time.perf_counter()
        rows, ctrl = check.gaps(ref, cell.spec, params,
                                [np.asarray(s.req.tokens) for s in picked],
                                [s.prompt_len for s in picked], controls)
        del params
        log(f"reference: {len(picked)} requests, "
            f"{sum(len(g) for g in rows)} served tokens, engines "
            f"{sorted({s.engine for s in picked})}, "
            f"{time.perf_counter() - t:.3f} s")
    limits = {"gap_mean": lim["gap_mean"], "wrong_length": 0.0,
              "out_of_vocab": 0.0}

    def verdict(gap_rows, who):
        values = dict(base, **check.readings(gap_rows))
        log(f"widest gap, {who} (not compared): {values.pop('gap_max')}")
        ok, table = check.judge(values, limits)
        for v in table.values():
            if not np.isfinite(v["value"]):
                v["value"] = None
        return ok, table

    ok, table = verdict(rows, "program")
    if not controls:
        return ok, table
    return ok, table, {m: verdict(ctrl[m], f"control {m}") for m in controls}
