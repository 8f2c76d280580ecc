"""Seeded random weights, made on the device in one jitted call.

Each block module (``bench/blocks/<block>.py``) builds the tree the serving
engine takes (stacked ``[L, ...]`` blocks, bf16 matrices, f32 norm gains)
from the leaves below.  Nothing here imports the program, so the plain
reference regenerates the very same values from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit key from a seed of any size (seeds may exceed 32 bits)."""
    return int(np.random.SeedSequence([int(seed), salt]).generate_state(1)[0])


def leaf(key, shape, kind):
    """A ``gain`` (f32 near 1, so a dropped gain shows) or a ``dense``
    matrix (bf16 at 1/sqrt(fan-in))."""
    if kind == "gain":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    scale = 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)


def make_weights(model: dict, seed: int) -> dict:
    """All weights of ``model`` (a configuration file's dict) from ``seed``,
    as its block makes them."""
    from bench import blocks

    return blocks.of(model).make_weights(model, seed)
