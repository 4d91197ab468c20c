"""Random weights from the run seed, for the program and the reference.

The benchmark makes the weights itself, so that the reference can make
the same ones again without taking anything from the program.  Every leaf
of every layer has its own key, ``fold_in(fold_in(model_key, leaf), layer)``,
so one layer's values can be drawn alone (the reference, layer by layer)
or all layers at once into the stacked ``(L, ...)`` leaves the program
serves from (one jitted call on the device, in bf16).

Which leaves a model has, their ids and shapes, and the program's layout
of them are its family's (``bench/families/<family>.py``); this module
holds the key derivation they all draw from and the shared scales:
embedding N(0, 0.02), each projection N(0, 1 / fan_in), norm offsets
N(0, 0.1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_STD = 0.1
EMBED_STD = 0.02


def run_key(seed: int):
    """A PRNG key from any non-negative integer seed (64-bit included)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def model_key(seed: int, index: int):
    """Key of model ``index`` (0 edge, 1 cloud) under run seed ``seed``."""
    return jax.random.fold_in(run_key(seed), index)


def _leaf(key, leaf: int, shape, std: float, dtype, layer=None):
    """The values of leaf id ``leaf`` (of layer ``layer``, or outside the
    layers), N(0, std) drawn in float32 and cast to ``dtype``.  A family
    owns its ids: once used, an id is never renumbered."""
    k = jax.random.fold_in(key, leaf)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def on_device(seed: int, index: int, build):
    """One jitted call of ``build(model_key)`` on the device: model
    ``index``'s served weights under run seed ``seed``."""
    key = model_key(seed, index)
    return jax.block_until_ready(jax.jit(build)(key))
