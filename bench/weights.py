"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the serving engine takes (stacked ``[L, ...]``
blocks, bf16 matrices, f32 norm gains).  Nothing here imports the program,
so the plain reference regenerates the very same values from the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# leaf -> (shape builder, init kind); the order fixes each leaf's PRNG stream
_BLOCK_LEAVES = (
    ("norm1", "gain"),
    ("wq", "dense"),
    ("wk", "dense"),
    ("wv", "dense"),
    ("wo", "dense"),
    ("norm2", "gain"),
    ("wg", "dense"),
    ("wu", "dense"),
    ("wd", "dense"),
)


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit key from a seed of any size (seeds may exceed 32 bits)."""
    return int(np.random.SeedSequence([int(seed), salt]).generate_state(1)[0])


def block_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    d, f = m["hidden_size"], m["intermediate_size"]
    h, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return {
        "norm1": (d,),
        "wq": (d, h * hd),
        "wk": (d, hkv * hd),
        "wv": (d, hkv * hd),
        "wo": (h * hd, d),
        "norm2": (d,),
        "wg": (d, f),
        "wu": (d, f),
        "wd": (f, d),
    }


def _leaf(key, shape, kind):
    if kind == "gain":  # norm gains near 1, so a dropped gain shows
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    scale = 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)


def _block(key, m):
    shapes = block_shapes(m)
    keys = jax.random.split(key, len(_BLOCK_LEAVES))
    return {
        name: _leaf(k, shapes[name], kind)
        for k, (name, kind) in zip(keys, _BLOCK_LEAVES)
    }


@functools.partial(jax.jit, static_argnames=("mtuple",))
def _make(key, mtuple):
    m = dict(mtuple)
    d, v, n_layers = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    k_embed, k_head, k_norm, k_blocks = jax.random.split(key, 4)
    blocks = jax.vmap(lambda k: _block(k, m))(jax.random.split(k_blocks, n_layers))
    return {
        "embed": (jax.random.normal(k_embed, (v, d), jnp.float32) * 0.5).astype(
            jnp.bfloat16
        ),
        "unembed": _leaf(k_head, (d, v), "dense"),
        "final_norm": _leaf(k_norm, (d,), "gain"),
        "blocks": {
            "norm1": blocks["norm1"],
            "wq": blocks["wq"],
            "wk": blocks["wk"],
            "wv": blocks["wv"],
            "wo": blocks["wo"],
            "norm2": blocks["norm2"],
            "mlp": {"wg": blocks["wg"], "wu": blocks["wu"], "wd": blocks["wd"]},
        },
    }


def model_dims(model: dict) -> tuple:
    """The hashable subset of a configuration file that fixes the weights."""
    keys = (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
    )
    return tuple((k, int(model[k])) for k in keys)


def make_weights(model: dict, seed: int) -> dict:
    """All weights of ``model`` (a configuration file's dict) from ``seed``."""
    return _make(jax.random.PRNGKey(seed32(seed, 1)), model_dims(model))
