"""Per-instance serving engine: continuous batching over the JAX model.

This is the functional engine the proxy routes to — it runs real prefill
and decode steps (the same ``repro.models`` code the dry-run lowers for
TPU), manages request lifecycles, reports the black-box timing events the
EMA estimator consumes, and supports token-ID checkpointing of in-flight
requests (the migration/fault-tolerance path).  Each engine lives on one
device, which holds its params and cache (one engine per chip): on CPU it
serves reduced configs, on a TPU chip published widths with the depth
cut to what one chip holds (see ``repro.launch.serve``).

Chunked prefill (``prefill_chunk=N``): instead of admitting a prompt as
one monolithic prefill that stalls every co-batched decode, the queue
head is staged into a linear scratch cache and advanced at most N prompt
tokens per ``step()``, interleaved with the decode batch — Sarathi/
AccelGen-style iteration shaping, which is what keeps decode TPOT stable
under long-prompt arrivals.  Only full/window-attention configs qualify
(mamba/MLA states are not chunk-resumable); others silently keep the
one-shot path.

Spans: the engine marks what the host does in a step with
``jax.profiler.TraceAnnotation`` spans, each carrying ``dev`` (the
engine's device id), so a profiler trace says what the host was doing
in every stretch the device sat idle.  Per admitted request,
``engine.admit`` (``rid``, ``prompt_len``, ``queued_ms``) holds
``engine.prefill`` (dispatch), ``engine.sync`` (the host waiting for the
first token) and ``engine.splice`` (dispatch); a decode runs
``engine.inputs`` (token array and ``device_put``), ``engine.decode``
(dispatch), ``engine.sync`` (waiting for the next tokens) and
``engine.commit`` (tokens appended, completion, kept logits).  Every
place where the host blocks on the device is an ``engine.sync`` span.
The programs are jitted module-level functions, so they carry their names
(``jit_engine_decode``, ``jit_engine_prefill``,
``jit_engine_prefill_chunk``) in the trace.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.distributed.context import NULL_CTX, ShardCtx
from repro.models import (decode_step, init_cache, init_params, prefill,
                          prefill_chunk, ring_convert_cache)


@dataclasses.dataclass
class EngineRequest:
    rid: int
    tokens: List[int]                 # prompt + generated so far
    prompt_len: int
    max_new_tokens: int = 64
    eos_id: Optional[int] = None
    done: bool = False
    # keep_logits: the engine appends the [V] logits row (numpy) of every
    # token it generates for this request to ``logits``
    keep_logits: bool = False
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    # host times (time.perf_counter) the engine stamps: submitted, its
    # admission began, its first token reached the host
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None

    @property
    def generated(self) -> List[int]:
        return self.tokens[self.prompt_len:]


class InferenceEngine:
    """Static-batch continuous decoding engine (batch slots + shared
    dense cache; the paged Pallas kernel is the TPU fast path).

    ``device`` (default ``jax.devices()[0]``) holds the params and the
    cache; ``params=None`` initialises them there from ``seed``, so
    replicas built with one seed serve one model."""

    def __init__(self, cfg: ModelConfig, params=None, *, max_batch: int = 8,
                 max_len: int = 256, ctx: ShardCtx = NULL_CTX, seed: int = 0,
                 greedy: bool = True, prefill_chunk: Optional[int] = None,
                 max_events: int = 4096, device=None):
        self.cfg = cfg
        self.ctx = ctx
        self.max_batch = max_batch
        self.max_len = max_len
        self.greedy = greedy
        self.device = device if device is not None else jax.devices()[0]
        self._dev = self.device.id          # the spans' ``dev`` argument
        on_device = SingleDeviceSharding(self.device)
        if params is None:
            params = jax.jit(
                lambda k: init_params(cfg, k, dtype=jnp.float32),
                out_shardings=on_device)(jax.random.PRNGKey(seed))
        self.params = jax.device_put(params, on_device)
        self.cache = jax.jit(
            lambda: init_cache(cfg, max_batch, max_len, dtype=jnp.float32),
            out_shardings=on_device)()
        self._staging_cache = jax.jit(
            lambda: init_cache(cfg, 1, max_len, dtype=jnp.float32,
                               ring=False),
            out_shardings=on_device)
        self.slots: List[Optional[EngineRequest]] = [None] * max_batch
        self.queue: List[EngineRequest] = []
        self._decode = functools.partial(DECODE, cfg=cfg, ctx=ctx)
        # one compile per prompt length
        self._prefill = functools.partial(PREFILL, cfg=cfg, max_len=max_len,
                                          ctx=ctx)
        self._splice = jax.jit(splice_cache, donate_argnums=0)
        # chunked prefill: only full/window mixers are chunk-resumable
        chunkable = all(blk.mixer in ("full", "window")
                        for blk in cfg.layer_list())
        self.prefill_chunk = (prefill_chunk
                              if (prefill_chunk and chunkable) else None)
        self._chunk_fn = functools.partial(PREFILL_CHUNK, cfg=cfg, ctx=ctx)
        # one request staged at a time: (slot, req, linear cache, t0,
        # tokens consumed, last-chunk logits)
        self._staging: Optional[dict] = None
        # timing observations for the estimator (black-box signals):
        # bounded ring — consumers call drain_events(), stragglers don't
        # leak memory on long-running engines
        self.events: Deque[tuple] = deque(maxlen=max_events)
        self.completed: List[EngineRequest] = []

    # -- request lifecycle -----------------------------------------------------

    def submit(self, req: EngineRequest):
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    @property
    def pending(self) -> int:
        """Requests queued, staged or running: the router's load signal."""
        return (len(self.queue) + (self._staging is not None)
                + sum(r is not None for r in self.slots))

    def drain_events(self) -> List[tuple]:
        """Hand the accumulated (kind, size, dt) timing events to the
        caller and clear the buffer — the estimator-facing consumer API."""
        ev = list(self.events)
        self.events.clear()
        return ev

    def checkpoint_request(self, rid: int) -> Optional[EngineRequest]:
        """Token-ID snapshot of an in-flight request (migration / failure
        resubmission): frees its slot, returns the portable state."""
        if self._staging is not None and self._staging["req"].rid == rid:
            req = self._staging["req"]
            self._staging = None        # partial prefill is discarded:
            return req                  # token IDs re-prefill at the target
        for i, r in enumerate(self.slots):
            if r is not None and r.rid == rid:
                self.slots[i] = None
                return r
        for r in self.queue:
            if r.rid == rid:
                self.queue.remove(r)
                return r
        return None

    # -- admission: one-shot and chunked prefill ------------------------------

    def _admit(self):
        for i in range(self.max_batch):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                t0 = req.t_admit = time.perf_counter()
                with self._admit_span(req):
                    self._prefill_into_slot(i, req)
                self.events.append(("prefill", req.prompt_len,
                                    time.perf_counter() - t0))

    def _admit_span(self, req: EngineRequest):
        return TraceAnnotation(
            "engine.admit", dev=self._dev, rid=req.rid,
            prompt_len=req.prompt_len,
            queued_ms=1e3 * (req.t_admit - req.t_submit))

    def _to_device(self, x: np.ndarray):
        return jax.device_put(x, self.device)

    def _emit_first(self, slot: int, req: EngineRequest, logits, nxt: int):
        if req.keep_logits:
            # sliced on the host: a slice on the device would queue
            # behind the splice and wait for it
            req.logits.append(np.asarray(logits)[0])
        req.tokens.append(nxt)
        self.slots[slot] = req

    def _prefill_into_slot(self, slot: int, req: EngineRequest):
        with TraceAnnotation("engine.prefill", dev=self._dev):
            toks = self._to_device(np.asarray(req.tokens, np.int32)[None])
            logits, nxt, cache1 = self._prefill(self.params, toks)
        with TraceAnnotation("engine.sync", dev=self._dev):
            nxt = int(nxt[0])
        req.t_first = time.perf_counter()
        with TraceAnnotation("engine.splice", dev=self._dev):
            self.cache = self._splice(self.cache, cache1, np.int32(slot),
                                      np.int32(len(req.tokens)))
        self._emit_first(slot, req, logits, nxt)

    def _advance_staged(self):
        """Begin and/or advance the staged prefill by at most one chunk —
        the per-iteration prefill-token budget."""
        if self._staging is None:
            free = next((i for i, r in enumerate(self.slots) if r is None),
                        None)
            if free is None or not self.queue:
                return
            req = self.queue.pop(0)
            req.t_admit = time.perf_counter()
            self._staging = {
                "slot": free, "req": req, "cache": self._staging_cache(),
                "t0": req.t_admit, "done": 0}
        with self._admit_span(self._staging["req"]):
            self._advance_chunk(self._staging)

    def _advance_chunk(self, st: dict):
        req, C = st["req"], self.prefill_chunk
        n = min(C, req.prompt_len - st["done"])
        with TraceAnnotation("engine.prefill", dev=self._dev):
            toks = np.zeros((1, C), np.int32)
            toks[0, :n] = req.tokens[st["done"]:st["done"] + n]
            logits, st["cache"] = self._chunk_fn(
                self.params, st["cache"], self._to_device(toks),
                self._to_device(np.asarray([n], np.int32)))
        st["done"] += n
        if st["done"] < req.prompt_len:
            return
        # prompt complete: ring-convert, splice, emit the first token
        with TraceAnnotation("engine.splice", dev=self._dev):
            ring = ring_convert_cache(self.cfg, st["cache"], self.max_len,
                                      req.prompt_len)
            self.cache = self._splice(self.cache, ring, np.int32(st["slot"]),
                                      np.int32(req.prompt_len))
        with TraceAnnotation("engine.sync", dev=self._dev):
            nxt = int(jnp.argmax(logits[0]))
        req.t_first = time.perf_counter()
        self._emit_first(st["slot"], req, logits, nxt)
        self.events.append(("prefill", req.prompt_len,
                            time.perf_counter() - st["t0"]))
        self._staging = None

    def step(self) -> int:
        """One engine iteration; returns number of active requests."""
        if self.prefill_chunk:
            self._advance_staged()
        else:
            self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        with TraceAnnotation("engine.inputs", dev=self._dev):
            toks = np.zeros((self.max_batch, 1), np.int32)
            for i in active:
                toks[i, 0] = self.slots[i].tokens[-1]
            # the clock stops once the next tokens are on the host:
            # dispatch is asynchronous, so stopping at the call would
            # time the enqueue
            t0 = time.perf_counter()
            toks = self._to_device(toks)
        with TraceAnnotation("engine.decode", dev=self._dev):
            logits, nxt, self.cache = self._decode(self.params, self.cache,
                                                   toks)
        with TraceAnnotation("engine.sync", dev=self._dev):
            nxt = np.asarray(nxt)
        self.events.append(("decode", len(active),
                            time.perf_counter() - t0))
        with TraceAnnotation("engine.commit", dev=self._dev):
            self._commit(active, logits, nxt)
        return len(active)

    def _commit(self, active: List[int], logits, nxt: np.ndarray):
        """Append each active request's next token, retire the finished
        ones and copy the kept logits rows."""
        kept = (np.asarray(logits)
                if any(self.slots[i].keep_logits for i in active) else None)
        for i in active:
            req = self.slots[i]
            if req.keep_logits:
                req.logits.append(kept[i])
            req.tokens.append(int(nxt[i]))
            full = len(req.tokens) >= min(
                req.prompt_len + req.max_new_tokens, self.max_len - 1)
            if full or (req.eos_id is not None
                        and int(nxt[i]) == req.eos_id):
                req.done = True
                self.completed.append(req)
                self.slots[i] = None

    def run_until_drained(self, max_iters: int = 10000):
        for _ in range(max_iters):
            n = self.step()
            if n == 0 and not self.queue and self._staging is None:
                break
        return self.completed


def engine_decode(params, cache, tokens, *, cfg: ModelConfig,
                  ctx: ShardCtx = NULL_CTX):
    """One decode iteration plus its greedy pick: (logits [B, V],
    next tokens [B], new cache)."""
    logits, cache = decode_step(params, cfg, cache, tokens, ctx=ctx)
    return logits, jnp.argmax(logits, axis=-1), cache


def engine_prefill(params, tokens, *, cfg: ModelConfig, max_len: int,
                   ctx: ShardCtx = NULL_CTX):
    """Prompt prefill plus its greedy pick: (logits [B, V], next tokens
    [B], ring-layout cache sized ``max_len``)."""
    logits, cache = prefill(params, cfg, tokens, max_len=max_len, ctx=ctx)
    return logits, jnp.argmax(logits, axis=-1), cache


# the decode's earlier name, which the benchmark's fault tests import
greedy_decode = engine_decode


def splice_cache(cache, one, slot, pos):
    """Copy a single-request (ring-layout) cache ``one`` into the batch
    cache at ``slot`` and set that slot's next position to ``pos``."""
    stages = jax.tree.map(
        lambda b, o: lax.dynamic_update_slice_in_dim(b, o, slot, axis=1),
        cache["stages"], one["stages"])
    return {"stages": stages, "pos": cache["pos"].at[slot].set(pos)}


def engine_prefill_chunk(params, cache, tokens, n_valid, *,
                         cfg: ModelConfig, ctx: ShardCtx = NULL_CTX):
    """One staged chunk of a chunked prefill."""
    return prefill_chunk(params, cfg, cache, tokens, n_valid=n_valid,
                         ctx=ctx)


# The engine's programs: module-level functions jitted with the model
# configuration static, so that the trace names them after the function.
# The decode donates the cache, so the old and the new cache never
# coexist on the device.
DECODE = jax.jit(engine_decode, static_argnames=("cfg", "ctx"),
                 donate_argnums=1)
PREFILL = jax.jit(engine_prefill, static_argnames=("cfg", "max_len", "ctx"))
PREFILL_CHUNK = jax.jit(engine_prefill_chunk, static_argnames=("cfg", "ctx"))

