"""Serving launcher, chip smoke and compile-cache tests, on CPU at the
reduced size: routing across replicas, the one-chip depth cut, the
smoke's phases and checks, and where the compile cache lives."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import get_config, reduce_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import serve as launcher  # noqa: E402


@pytest.fixture(scope="module")
def size():
    return chip_smoke.Size(reduce_config(get_config("minicpm-2b")),
                           max_batch=4, max_len=96, prompt_lens=(8, 16, 24))


def test_chip_config_keeps_published_widths_and_cuts_depth():
    full, cfg = get_config("minicpm-2b"), launcher.chip_config("minicpm-2b")
    assert (cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (2304, 36, 64, 5760, 122753)
    assert (full.d_model, full.d_ff, full.vocab_size) == \
        (cfg.d_model, cfg.d_ff, cfg.vocab_size)
    assert cfg.num_layers == launcher.DEPTH_CUTS["minicpm-2b"].layers == 20
    assert len(cfg.layer_list()) == 20
    with pytest.raises(ValueError, match="no one-chip depth"):
        launcher.chip_config("llama3.1-8b")


def test_router_spreads_a_closed_batch_over_replicas(size):
    dev = jax.devices()[0]
    engines = launcher.build_engines(size.cfg, [dev, dev, dev],
                                     max_batch=size.max_batch,
                                     max_len=size.max_len)
    reqs = launcher.make_requests(size.cfg, 7, size.prompt_lens, 3, seed=1)
    res = launcher.serve(engines, reqs)
    assert sorted(r.rid for r in res.completed) == list(range(7))
    counts = [list(res.served_by.values()).count(i) for i in range(3)]
    assert counts == [3, 2, 2]
    assert res.tokens == 7 * 3
    # replicas built from one seed hold the same weights
    a, b = (jax.tree.leaves(e.params) for e in engines[:2])
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_make_requests_is_seeded_with_few_prompt_lengths(size):
    a = launcher.make_requests(size.cfg, 8, size.prompt_lens, 4, seed=3)
    b = launcher.make_requests(size.cfg, 8, size.prompt_lens, 4, seed=3)
    assert [r.tokens for r in a] == [r.tokens for r in b]
    assert {r.prompt_len for r in a} == set(size.prompt_lens)


def test_serve_main_reduced(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    launcher.main(["--reduced", "--engines", "2", "--n-requests", "4",
                   "--max-new", "3"])
    out = capsys.readouterr().out
    assert "served 4 requests of minicpm-2b-reduced" in out
    assert "engine1: served=2" in out


def test_smoke_one_chip_phase_matches_model_forward(size):
    out = chip_smoke.one_chip_phase(size, jax.devices()[0], seed=0)
    assert len(out["result"].completed) == chip_smoke.N_REQUESTS
    assert sorted(out["errors"]) == [0, 1, 2]        # one per prompt length
    assert max(out["errors"].values()) < 1e-4       # float32 on CPU


def test_smoke_replicas_phase_agrees_with_one_engine(size):
    dev = jax.devices()[0]
    out = chip_smoke.replicas_phase(size, [dev, dev], seed=0)
    assert sorted(out["result"].served_by.values()) == [0] * 4 + [1] * 4
    assert set(out["agree"].values()) == {chip_smoke.MAX_NEW}
    assert max(out["errors"].values()) < 1e-5


def test_logit_error_is_worst_relative_row():
    ref = np.array([[3.0, 4.0], [1.0, 0.0]])
    got = ref + np.array([[0.0, 0.5], [0.0, 0.0]])
    assert chip_smoke.logit_error(got, ref) == pytest.approx(0.1)


def test_smoke_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "no TPU found" in str(e.value.code)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    was = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.setup_compile_cache()
        # with the variable set, the function sets no directory itself
        assert got == (was if env_dir else str(compile_cache.DEFAULT_DIR))
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert str(compile_cache.DEFAULT_DIR) == os.path.join(root, ".jax_cache")


def test_profile_engine_refuses_cpu(monkeypatch, tmp_path):
    from benchmarks import profile as bench_profile
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit, match="--engine measures a TPU"):
        bench_profile.main(["--engine", "--out", str(tmp_path)])


def test_kernels_interpret_only_on_cpu(monkeypatch):
    from repro.kernels import interpret_mode
    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on backend 'gpu'"):
        interpret_mode()
