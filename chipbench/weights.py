"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights, not the program: the reference can
then make the same ones again from the seed without taking anything the
program made.  Each leaf's values follow the law that the configuration's
reference module gives for its name (``draw``); the tree of leaves, with
its shapes and dtypes, is the layout the program takes.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import SingleDeviceSharding


def key_words(seed: int) -> np.ndarray:
    """The seed as the two 32-bit words of a threefry key: every seed up
    to 2**64 gets its own key."""
    seed %= 2 ** 64
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _make(draw, spec, shapes, words):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for i, (path, sds) in enumerate(flat):
        name = path[-1].key
        v = draw(name, jax.random.fold_in(key, i), sds.shape, spec)
        leaves.append(v.astype(sds.dtype))
    return tree.unflatten(leaves)


def generate(draw, spec, shapes, seed: int, device):
    """Weights with the tree and shapes of ``shapes`` (a tree of
    ``ShapeDtypeStruct``), on ``device``."""
    fn = jax.jit(functools.partial(_make, draw, spec, shapes),
                 out_shardings=SingleDeviceSharding(device))
    return fn(jax.device_put(key_words(seed), device))
