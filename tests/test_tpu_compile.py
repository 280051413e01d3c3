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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import Stage, uniform_stage
from repro.engine.engine import DECODE, engine_prefill
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.paged_attention.paged_attention import \
    paged_attention_pallas
from repro.kernels.ssd.ssd import ssd_pallas
from repro.launch.serve import CHIP_BATCH, CHIP_MAX_LEN, chip_config
from repro.models import init_cache, init_params, serving_params
from repro.models.model import MATMUL_WEIGHTS

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
    DECODE.lower(params, cache, toks, cfg=cfg).compile()


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
    compiled = DECODE.lower(params, cache, toks, cfg=cfg).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0   # donated
    assert _total_bytes(compiled) < V5E_USABLE_BYTES


def test_minicpm_decode_keeps_one_cache_buffer(one_chip, minicpm_2_layers):
    """The decode stores each new token's K/V rows into the donated cache
    in place: no copy of the stacked cache, in any layout, is made."""
    cfg = minicpm_2_layers
    params, cache = _model_shapes(cfg, one_chip, CHIP_BATCH, CHIP_MAX_LEN)
    toks = _spec(one_chip, (CHIP_BATCH, 1), jnp.int32)
    compiled = DECODE.lower(params, cache, toks, cfg=cfg).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0   # donated
    stack = ",".join(map(str, cache["stages"][0]["blk0"]["k"].shape))
    copies = re.findall(rf"= f32\[{stack}\]\{{[^}}]*\}} copy\(",
                        compiled.as_text())
    assert not copies, copies


@pytest.fixture(scope="module")
def deepseek_2_layers():
    """DeepSeek-V2-Lite at published widths: the dense layer 0 and one MoE
    layer holding 8 of its 64 routed experts."""
    cfg = get_config("deepseek-v2-lite-16b")
    dense, moe = (st.pattern for st in cfg.stages)
    return dataclasses.replace(
        cfg, num_layers=2, stages=(Stage(dense, 1), Stage(moe, 1)),
        moe=dataclasses.replace(cfg.moe, experts_held=8))


DEEPSEEK_BATCH, DEEPSEEK_MAX_LEN = 32, 2048


def test_deepseek_decode_compiles_at_full_width(one_chip, deepseek_2_layers):
    cfg = deepseek_2_layers
    params, cache = _model_shapes(cfg, one_chip, DEEPSEEK_BATCH,
                                  DEEPSEEK_MAX_LEN)
    toks = _spec(one_chip, (DEEPSEEK_BATCH, 1), jnp.int32)
    DECODE.lower(params, cache, toks, cfg=cfg).compile()


def test_deepseek_prefill_compiles_at_full_width(one_chip, deepseek_2_layers):
    """A prompt whose length is not a multiple of the attention's query
    chunk, its held experts in one grouped-matmul kernel."""
    cfg = deepseek_2_layers
    params, _ = _model_shapes(cfg, one_chip, 1, DEEPSEEK_MAX_LEN)
    fn = jax.jit(lambda p, t: engine_prefill(p, t, cfg=cfg,
                                             max_len=DEEPSEEK_MAX_LEN))
    compiled = fn.lower(params, _spec(one_chip, (1, 1664), jnp.int32)) \
        .compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_deepseek_cell_decode_fits_one_chip_and_copies_no_latent_cache(
        one_chip):
    """The benchmark cell's decode (14 layers, 8 held experts a layer,
    batch 32 of 2048 latent positions, cache donated) fits the memory one
    v5e chip leaves to programs and writes its latent rows in place: no
    copy of a stacked latent cache is made, in any dtype or layout.  The
    one other buffer of a stacked latent cache's shape is its bfloat16
    image, which default precision feeds the matrix unit: the compiler
    converts the whole stack once, ahead of the layer loop, since the
    loop only reads it (about 4 ms of a decode step on the chip, the same
    bytes as converting each layer's slice inside the loop).  A float32
    or second converted stack fails the test."""
    import json

    from chipbench.harness import ROOT
    from chipbench.program import deepseek_v2
    spec = json.loads((ROOT / "chipbench" / "configs"
                       / "deepseek-v2-lite.json").read_text())
    cfg = deepseek_v2.config(spec)
    B, S = spec["serve"]["max_batch"], spec["serve"]["max_len"]
    params, cache = _model_shapes(cfg, one_chip, B, S)
    compiled = DECODE.lower(params, cache, _spec(one_chip, (B, 1), jnp.int32),
                            cfg=cfg).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0   # donated
    assert _total_bytes(compiled) < V5E_USABLE_BYTES
    text = compiled.as_text()
    for stage in cache["stages"]:
        for leaf in stage["blk0"].values():
            stack = ",".join(map(str, leaf.shape))
            made = re.findall(rf"= (\w+)\[{stack}\]\{{[^}}]*\}} "
                              rf"(copy|copy-start|convert)\(", text)
            assert made in ([], [("bf16", "convert")]), made


# The weights an engine serves from on a TPU (``serving_params``): the
# dots read the bfloat16 stacks as they are, so no convert of a weight
# stack, or of one layer's slice of it, is left in either direction.

@pytest.fixture(scope="module")
def mamba_2_layers():
    cfg = get_config("mamba2-1.3b")
    (stage,) = cfg.stages
    return dataclasses.replace(cfg, num_layers=2,
                               stages=(Stage(stage.pattern, 2),))


@pytest.fixture(scope="module")
def deepseek_3_layers(deepseek_2_layers):
    """``deepseek_2_layers`` with two MoE layers: a stage of one layer
    runs no loop, so nothing could be hoisted out of one."""
    dense, moe = deepseek_2_layers.stages
    return dataclasses.replace(deepseek_2_layers, num_layers=3,
                               stages=(dense, Stage(moe.pattern, 2)))


# fixture: (batch, max_len, prompt length of the prefill)
SERVED = {"minicpm_2_layers": (CHIP_BATCH, CHIP_MAX_LEN, 512),
          "mamba_2_layers": (32, 2048, 1664),
          "deepseek_3_layers": (DEEPSEEK_BATCH, DEEPSEEK_MAX_LEN, 1664)}


def _compiled_text(cfg, sharding, program, batch, max_len, prompt, served):
    params, cache = _model_shapes(cfg, sharding, batch, max_len)
    if served:
        params = _on(sharding, jax.eval_shape(serving_params, params))
    if program == "decode":
        fn = DECODE.lower(params, cache,
                          _spec(sharding, (batch, 1), jnp.int32),
                          cfg=cfg)
    else:
        fn = jax.jit(lambda p, t: engine_prefill(
            p, t, cfg=cfg, max_len=max_len)).lower(
                params, _spec(sharding, (1, prompt), jnp.int32))
    return params, fn.compile().as_text()


def _weight_stack_converts(params, text):
    """(dtype, shape) of every convert whose result has the shape of a
    matmul weight stack or of one layer's slice of it; a convert's
    operand has its result's shape, so both directions are caught."""
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params["stages"])[0]:
        if path[-1].key in MATMUL_WEIGHTS:
            shapes |= {",".join(map(str, leaf.shape)),
                       ",".join(map(str, leaf.shape[1:]))}
    made = re.findall(r"= (\w+)\[([\d,]*)\]\{[^}]*\} convert\(", text)
    return [m for m in made if m[1] in shapes]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("model", sorted(SERVED))
def test_served_weights_reach_the_dots_unconverted(one_chip, request, model,
                                                   program):
    cfg = request.getfixturevalue(model)
    params, text = _compiled_text(cfg, one_chip, program, *SERVED[model],
                                  served=True)
    assert _weight_stack_converts(params, text) == []


def test_float32_weights_are_converted_every_call(one_chip,
                                                  minicpm_2_layers):
    """The control: float32 stacks, as the engine holds them on a CPU,
    are each converted to bfloat16 by the decode, ahead of its layer
    loop (the cost the served weights take away)."""
    params, text = _compiled_text(minicpm_2_layers, one_chip, "decode",
                                  *SERVED["minicpm_2_layers"], served=False)
    made = _weight_stack_converts(params, text)
    assert sorted(dt for dt, _ in made) == ["bf16"] * 7, made
