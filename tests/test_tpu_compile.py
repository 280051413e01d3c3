"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode and the CPU backend accept:
block shapes off the (8, 128) tiling, primitives Mosaic cannot lower,
programs that overflow the chip's memory.  These cases compile the
kernels and the serving engine's programs at the widths the chip runs,
so such faults show up here and not on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports every
test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import uniform_stage
from repro.distributed.context import NULL_CTX
from repro.engine.engine import DECODE, engine_prefill
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.paged_attention.paged_attention import \
    paged_attention_pallas
from repro.kernels.ssd.ssd import ssd_pallas
from repro.launch.serve import CHIP_BATCH, CHIP_MAX_LEN, chip_config
from repro.models import init_cache, init_params

# what one v5e chip leaves to programs of its 16 GB of HBM
V5E_USABLE_BYTES = 15.75e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)


def _model_shapes(cfg, sharding, batch, max_len):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_params(cfg, k, jnp.float32), key)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, dtype=jnp.float32))
    return _on(sharding, params), _on(sharding, cache)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles_at_minicpm_widths(one_chip, dtype):
    cfg = get_config("minicpm-2b")
    B, page = CHIP_BATCH, 16
    n_pages = CHIP_MAX_LEN // page
    kv_shape = (B * n_pages, page, cfg.num_kv_heads, cfg.head_dim)
    fn = jax.jit(lambda *a: paged_attention_pallas(*a, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (B, cfg.num_heads, cfg.head_dim), dtype),
        _spec(one_chip, kv_shape, dtype), _spec(one_chip, kv_shape, dtype),
        _spec(one_chip, (B, n_pages), jnp.int32),
        _spec(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles_at_minicpm_heads(one_chip, dtype):
    cfg = get_config("minicpm-2b")
    q = _spec(one_chip, (1, CHIP_MAX_LEN, cfg.num_heads, cfg.head_dim),
              dtype)
    kv = _spec(one_chip, (1, CHIP_MAX_LEN, cfg.num_kv_heads, cfg.head_dim),
               dtype)
    fn = jax.jit(lambda *a: flash_attention_pallas(*a, interpret=False))
    compiled = fn.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-1.3b")
    s = cfg.ssm
    H, L = s.n_heads(cfg.d_model), 4 * s.chunk
    fn = jax.jit(lambda *a: ssd_pallas(*a, chunk=s.chunk, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (1, L, H, s.head_dim)), _spec(one_chip, (1, L, H)),
        _spec(one_chip, (H,)),
        _spec(one_chip, (1, L, s.n_groups, s.d_state)),
        _spec(one_chip, (1, L, s.n_groups, s.d_state))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def minicpm_2_layers():
    return dataclasses.replace(get_config("minicpm-2b"), num_layers=2,
                               stages=uniform_stage(2))


def test_minicpm_decode_compiles_at_full_width(one_chip, minicpm_2_layers):
    cfg = minicpm_2_layers
    params, cache = _model_shapes(cfg, one_chip, CHIP_BATCH, CHIP_MAX_LEN)
    toks = _spec(one_chip, (CHIP_BATCH, 1), jnp.int32)
    DECODE.lower(params, cache, toks, cfg=cfg, ctx=NULL_CTX).compile()


def test_minicpm_prefill_compiles_at_full_width(one_chip, minicpm_2_layers):
    cfg = minicpm_2_layers
    params, _ = _model_shapes(cfg, one_chip, 1, CHIP_MAX_LEN)
    fn = jax.jit(lambda p, t: engine_prefill(p, t, cfg=cfg,
                                             max_len=CHIP_MAX_LEN))
    fn.lower(params, _spec(one_chip, (1, 512), jnp.int32)).compile()


def test_smoke_decode_fits_one_chip(one_chip):
    """The chip smoke's decode program (its layer cut, batch and max_len,
    cache donated) fits the memory one v5e chip leaves to programs."""
    cfg = chip_config("minicpm-2b")
    params, cache = _model_shapes(cfg, one_chip, CHIP_BATCH, CHIP_MAX_LEN)
    toks = _spec(one_chip, (CHIP_BATCH, 1), jnp.int32)
    compiled = DECODE.lower(params, cache, toks, cfg=cfg,
                            ctx=NULL_CTX).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0   # donated
    assert _total_bytes(compiled) < V5E_USABLE_BYTES
