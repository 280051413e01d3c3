"""Serving-engine tests: continuous batching, paged KV allocator,
token-ID request checkpointing (migration/FT), greedy decode equivalence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.engine.engine import EngineRequest, InferenceEngine
from repro.engine.kv_cache import PagedKVCache
from repro.models import init_params, model_forward
from repro.models.model import logits_fn

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def engine():
    cfg = reduce_config(get_config("llama3.1-8b"))
    return InferenceEngine(cfg, max_batch=3, max_len=64)


def test_engine_serves_batched_requests(engine):
    reqs = [EngineRequest(rid=i, tokens=list(range(5 + i, 13 + i)),
                          prompt_len=8 + 0 * i, max_new_tokens=6)
            for i in range(5)]
    for r in reqs:
        r.prompt_len = len(r.tokens)
        engine.submit(r)
    done = engine.run_until_drained()
    assert len(done) == 5
    for r in done:
        assert len(r.generated) >= 1


def test_engine_matches_teacher_forcing():
    cfg = reduce_config(get_config("llama3.1-8b"))
    eng = InferenceEngine(cfg, max_batch=2, max_len=48, seed=3)
    prompt = list(np.random.default_rng(0).integers(0, cfg.vocab_size, 10))
    r = EngineRequest(rid=0, tokens=list(prompt), prompt_len=len(prompt),
                      max_new_tokens=5)
    eng.submit(r)
    eng.run_until_drained()
    # greedy reference: argmax continuation under teacher forcing
    toks = list(prompt)
    for _ in range(len(r.generated)):
        h, _ = model_forward(eng.params, cfg,
                             jnp.asarray(toks, jnp.int32)[None],
                             remat=False)
        lg = logits_fn(eng.params, cfg, h[:, -1])
        toks.append(int(jnp.argmax(lg[0])))
    assert toks[len(prompt):] == r.generated


def test_token_id_checkpoint_roundtrip(engine):
    r = EngineRequest(rid=99, tokens=list(range(10)), prompt_len=10,
                      max_new_tokens=20)
    engine.submit(r)
    engine.step()
    snap = engine.checkpoint_request(99)
    assert snap is not None
    assert snap.tokens[:10] == list(range(10))
    # resubmit elsewhere: progress (generated tokens) is preserved
    assert len(snap.tokens) >= 10


def test_drain_events_bounded_and_clearing():
    cfg = reduce_config(get_config("llama3.1-8b"))
    eng = InferenceEngine(cfg, max_batch=2, max_len=48, max_events=4)
    for i in range(3):
        eng.submit(EngineRequest(rid=i, tokens=list(range(2, 9)),
                                 prompt_len=7, max_new_tokens=6))
    eng.run_until_drained()
    # the ring is bounded even though the run emitted more events
    assert len(eng.events) <= 4
    ev = eng.drain_events()
    assert 0 < len(ev) <= 4
    assert all(kind in ("prefill", "decode") for kind, _, _ in ev)
    assert eng.drain_events() == []          # drained means drained


def test_chunked_prefill_matches_oneshot():
    """Greedy continuations must be token-identical whether the prompt
    was prefetched in one shot or staged through the chunked path
    (including a final partial chunk)."""
    cfg = reduce_config(get_config("llama3.1-8b"))
    one = InferenceEngine(cfg, max_batch=2, max_len=64, seed=5)
    chk = InferenceEngine(cfg, one.params, max_batch=2, max_len=64,
                          prefill_chunk=8)
    assert chk.prefill_chunk == 8
    rng = np.random.default_rng(1)
    for rid, n in enumerate((17, 9)):        # 17: ragged final chunk
        prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, n)]
        one.submit(EngineRequest(rid=rid, tokens=list(prompt),
                                 prompt_len=n, max_new_tokens=6))
        chk.submit(EngineRequest(rid=rid, tokens=list(prompt),
                                 prompt_len=n, max_new_tokens=6))
    a = {r.rid: r.generated for r in one.run_until_drained()}
    b = {r.rid: r.generated for r in chk.run_until_drained()}
    assert a == b


def test_chunked_prefill_gates_off_for_mamba():
    """Non-resumable mixers must silently keep the one-shot path."""
    cfg = reduce_config(get_config("mamba2-1.3b"))
    eng = InferenceEngine(cfg, max_batch=1, max_len=48, prefill_chunk=8)
    assert eng.prefill_chunk is None
    eng.submit(EngineRequest(rid=0, tokens=list(range(1, 11)),
                             prompt_len=10, max_new_tokens=3))
    assert len(eng.run_until_drained()) == 1


def test_paged_cache_allocator():
    cfg = reduce_config(get_config("llama3.1-8b"))
    cache = PagedKVCache(cfg, num_pages=16, page_size=8)
    cache.allocate(1, 20)             # 3 pages
    cache.allocate(2, 8)              # 1 page
    assert cache.utilization() == pytest.approx(4 / 16)
    cache.extend(1, 5)                # 25 tokens -> 4 pages
    assert len(cache.tables[1]) == 4
    bt, lens = cache.batch_tables([1, 2])
    assert bt.shape == (2, 4)
    assert list(np.asarray(lens)) == [25, 8]
    cache.release(1)
    assert cache.utilization() == pytest.approx(1 / 16)
    with pytest.raises(MemoryError):
        cache.allocate(3, 16 * 8 + 1)


def test_paged_cache_exhaustion_on_extend():
    cfg = reduce_config(get_config("llama3.1-8b"))
    cache = PagedKVCache(cfg, num_pages=2, page_size=8)
    cache.allocate(1, 16)
    with pytest.raises(MemoryError):
        cache.extend(1, 1)


def test_engine_places_state_on_its_device_and_keeps_logits():
    cfg = reduce_config(get_config("llama3.1-8b"))
    dev = jax.devices()[-1]
    eng = InferenceEngine(cfg, max_batch=2, max_len=48, device=dev)
    leaves = jax.tree.leaves((eng.params, eng.cache))
    assert all(x.devices() == {dev} for x in leaves)
    r = EngineRequest(rid=0, tokens=list(range(1, 9)), prompt_len=8,
                      max_new_tokens=4, keep_logits=True)
    eng.submit(r)
    eng.step()                        # prefill, then the first decode
    old = eng.cache
    eng.step()
    # the decode step donates the cache: the old buffers are gone
    assert all(x.is_deleted() for x in jax.tree.leaves(old))
    eng.run_until_drained()
    assert len(r.logits) == len(r.generated) == 4
    assert [int(np.argmax(row)) for row in r.logits] == r.generated
