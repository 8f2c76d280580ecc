"""The dense pre-norm decoder block as published for LLaMA-style models:
RMSNorm, rotary embeddings (rotate-half), grouped-query causal softmax
attention (multi-head where ``num_key_value_heads == num_attention_heads``)
and a SwiGLU MLP, with an untied head.  The interface is described in
``bench/blocks/__init__.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, quantize_kv, quantize_weight, rms_norm, rope
from bench.weights import leaf, seed32

_REQUIRED = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
    "rope_theta", "rms_norm_eps",
)


def validate(model: dict) -> None:
    missing = [k for k in _REQUIRED if k not in model]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("only the SwiGLU (silu) block is served")
    if model["vocab_size"] % 256:
        raise ValueError("vocab_size must be a multiple of 256")


def arch_config(model: dict):
    """The engine's ArchConfig for a dense SwiGLU/RMSNorm/RoPE GQA block."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=model["name"],
        family="dense",
        n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        vocab=model["vocab_size"],
        head_dim=model["head_dim"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype="bfloat16",
    )


# ------------------------------------------------------------------ weights
# leaf -> init kind; the order fixes each leaf's PRNG stream
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


def _block(key, m):
    shapes = block_shapes(m)
    keys = jax.random.split(key, len(_BLOCK_LEAVES))
    return {
        name: leaf(k, shapes[name], kind)
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
        "unembed": leaf(k_head, (d, v), "dense"),
        "final_norm": leaf(k_norm, (d,), "gain"),
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
    """All weights of ``model`` from ``seed``."""
    return _make(jax.random.PRNGKey(seed32(seed, 1)), model_dims(model))


# ---------------------------------------------------------------- reference
@functools.partial(jax.jit, static_argnames=("bits",))
def layer_weights(blocks: dict, li: jnp.ndarray, bits: int) -> dict:
    """Layer ``li`` of the stacked bf16 blocks, in float32 at ``bits``."""
    take = lambda a: jax.lax.dynamic_index_in_dim(a, li, keepdims=False)
    p = {k: take(blocks[k]) for k in ("norm1", "norm2", "wq", "wk", "wv", "wo")}
    p.update({k: take(blocks["mlp"][k]) for k in ("wg", "wu", "wd")})
    for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
        p[k] = quantize_weight(p[k], bits)
    p["norm1"] = p["norm1"].astype(jnp.float32)
    p["norm2"] = p["norm2"].astype(jnp.float32)
    return p


@functools.partial(jax.jit, static_argnames=("dims", "kv_bits"))
def _layer(x: jnp.ndarray, p: dict, dims: tuple, kv_bits: int) -> jnp.ndarray:
    h, hkv, hd, theta, eps = dims
    t = x.shape[0]
    xn = rms_norm(x, p["norm1"], eps)
    mm = lambda a, w: jnp.matmul(a, w, precision=HI)
    q = rope(mm(xn, p["wq"]).reshape(t, h, hd), theta)
    k = quantize_kv(rope(mm(xn, p["wk"]).reshape(t, hkv, hd), theta), kv_bits)
    v = quantize_kv(mm(xn, p["wv"]).reshape(t, hkv, hd), kv_bits)
    q = q.reshape(t, hkv, h // hkv, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", a, v, precision=HI).reshape(t, h * hd)
    x = x + mm(o, p["wo"])
    xn = rms_norm(x, p["norm2"], eps)
    return x + mm(jax.nn.silu(mm(xn, p["wg"])) * mm(xn, p["wu"]), p["wd"])


def layer(x: jnp.ndarray, p: dict, model: dict, kv_bits: int) -> jnp.ndarray:
    """One block over a whole sequence x [T, d] (causal; padding at the end
    cannot reach earlier positions)."""
    dims = (
        model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"],
        float(model["rope_theta"]), float(model["rms_norm_eps"]),
    )
    return _layer(x, p, dims, kv_bits)
