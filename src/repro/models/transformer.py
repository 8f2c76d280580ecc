"""Model assembly for the whole LM fleet: dense / MoE / SSM / hybrid / VLM /
audio, with train, prefill and decode entry points.

Design notes (these matter at 512 devices):

  * **scan over layers** with stacked parameters — keeps the HLO size
    O(1) in depth, which is what makes 61-81-layer models lower/compile in
    minutes instead of hours at pod scale (MaxText-style).
  * **remat** (jax.checkpoint) per layer with a configurable policy.
  * **heterogeneous patterns without unrolling**: gemma3's 5:1 local:global
    and zamba2's shared-attention-every-6 are expressed as data (per-layer
    window vector / lax.cond on the step index) inside the scan, not as
    Python-unrolled layers.
  * **flash attention** (models/attention.py) everywhere — no [S, S] tensor.
  * **chunked cross-entropy** — no [tokens, vocab] tensor (262k vocabs).
  * **multi-precision serving** (the paper's technique): `quantize_params`
    converts every large matmul weight to int4/int8 QTensors consumed by the
    mpmm path, and the KV cache stores int8 payloads with per-(token, head)
    scales.

Cache layout: dict with stacked-leading-layer-dim arrays; decode steps scan
over layers carrying per-layer cache slices as scan xs/ys.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    chunked_cross_entropy,
    dense,
    dense_init,
    embed_init,
    quantize_dense_weight,
    rms_norm,
)

Params = dict[str, Any]
_GLOBAL_WINDOW = 1 << 30  # "no window" sentinel (dynamic window arithmetic)


# ================================================================ init ====
def _init_attn(key, cfg: ArchConfig, dtype) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "norm1": jnp.ones((d,), jnp.float32),
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kv * hd, dtype),
        "wv": dense_init(ks[2], d, kv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }


def _init_mlp(key, cfg: ArchConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "norm2": jnp.ones((d,), jnp.float32),
        "mlp": {
            "wg": dense_init(ks[0], d, f, dtype),
            "wu": dense_init(ks[1], d, f, dtype),
            "wd": dense_init(ks[2], f, d, dtype),
        },
    }


def _init_dense_block(key, cfg: ArchConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {**_init_attn(k1, cfg, dtype), **_init_mlp(k2, cfg, dtype)}


def _init_moe_block(key, cfg: ArchConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p = _init_attn(k1, cfg, dtype)
    p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
    p["moe"] = moe_mod.init_moe_params(k2, cfg.d_model, cfg.d_ff, cfg.n_experts, dtype)
    return p


def _init_ssm_block(key, cfg: ArchConfig, dtype) -> Params:
    dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)
    p = ssm_mod.init_ssm_params(key, dims, dtype)
    p["norm1"] = jnp.ones((cfg.d_model,), jnp.float32)
    return p


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(cfg: ArchConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype),
        "unembed": dense_init(keys[1], cfg.d_model, cfg.padded_vocab, dtype),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.family in ("dense", "vlm", "audio"):
        params["blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, dtype), keys[2], cfg.n_layers
        )
    elif cfg.family == "moe":
        if cfg.first_dense:
            params["dense_blocks"] = _stack_init(
                lambda k: _init_dense_block(k, cfg, dtype), keys[3], cfg.first_dense
            )
        params["blocks"] = _stack_init(
            lambda k: _init_moe_block(k, cfg, dtype),
            keys[2],
            cfg.n_layers - cfg.first_dense,
        )
    elif cfg.family == "ssm":
        params["blocks"] = _stack_init(
            lambda k: _init_ssm_block(k, cfg, dtype), keys[2], cfg.n_layers
        )
    elif cfg.family == "hybrid":
        n_groups = cfg.n_layers // cfg.attn_every
        rem = cfg.n_layers % cfg.attn_every
        params["blocks"] = _stack_init(
            lambda k: _init_ssm_block(k, cfg, dtype),
            keys[2],
            n_groups * cfg.attn_every,
        )
        if rem:
            params["tail"] = _stack_init(
                lambda k: _init_ssm_block(k, cfg, dtype), keys[4], rem
            )
        params["shared"] = _init_dense_block(keys[5], cfg, dtype)
    else:
        raise ValueError(cfg.family)
    return params


# ============================================================ quantize ====
_QUANT_KEYS = {"wq", "wk", "wv", "wo", "wg", "wu", "wd", "in_proj", "out_proj", "unembed"}


def quantize_params(params: Params, bits: int) -> Params:
    """The paper's technique on the serving path: every large matmul weight
    becomes an int4/int8 payload + per-output-channel scale.  Stacked [L, K,
    N] weights quantize layer-wise.  Embeddings stay bf16 (gather, not
    matmul); norms/router/ssm-vectors stay f32."""

    def walk(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                out[name] = walk(leaf)
            elif name in _QUANT_KEYS and getattr(leaf, "ndim", 0) >= 2:
                out[name] = quantize_dense_weight(leaf, bits)
            else:
                out[name] = leaf
        return out

    return walk(params)


# ======================================================== block applies ====
def _attn_block(p, x, positions, cfg: ArchConfig, window) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (attn_out [B,S,D], k, v) — k/v exposed for cache building."""
    from repro.models.layers import apply_rope

    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
    q = dense(xn, p["wq"]).reshape(b, s, h, hd)
    k = dense(xn, p["wk"]).reshape(b, s, kv, hd)
    v = dense(xn, p["wv"]).reshape(b, s, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "model", None)
    o = attn_mod.flash_attention(q, k, v, causal=True, window=window)
    o = dense(o.reshape(b, s, h * hd), p["wo"])
    return shard(o, "batch", None, None), k, v


def _mlp_block(p, x, cfg: ArchConfig) -> jnp.ndarray:
    xn = rms_norm(x, p["norm2"].astype(x.dtype), cfg.norm_eps)
    g = dense(xn, p["mlp"]["wg"])
    u = dense(xn, p["mlp"]["wu"])
    g = shard(g, "batch", None, "model")
    act = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) * u
    return shard(dense(act, p["mlp"]["wd"]), "batch", None, None)


def _moe_block(p, x, cfg: ArchConfig, mesh=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    xn = rms_norm(x, p["norm2"].astype(x.dtype), cfg.norm_eps)
    out, aux = moe_mod.moe_ffn(
        xn,
        p["moe"],
        top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor,
        dispatch=cfg.moe_dispatch,
        mesh=mesh,
    )
    return shard(out, "batch", None, None), aux


def _per_layer_window(cfg: ArchConfig, n: int) -> Optional[jnp.ndarray]:
    """Per-layer dynamic window vector, or None if uniform."""
    if cfg.local_ratio:
        period = cfg.local_ratio + 1
        idx = np.arange(n)
        is_global = (idx + 1) % period == 0
        return jnp.asarray(
            np.where(is_global, _GLOBAL_WINDOW, cfg.window), jnp.int32
        )
    return None


# ============================================================== forward ====
def _embed(params, batch, cfg: ArchConfig) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (x [B,S,D], positions [B,S], loss_mask [B,S])."""
    tokens = batch["tokens"]
    x = params["embed"].astype(jnp.dtype(cfg.dtype))[tokens]
    mask = jnp.ones(tokens.shape, jnp.float32)
    if cfg.prefix_len:
        pre = batch["prefix_emb"].astype(x.dtype)  # [B, P, D] (frontend stub)
        x = jnp.concatenate([pre, x], axis=1)
        mask = jnp.concatenate([jnp.zeros(pre.shape[:2], jnp.float32), mask], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = shard(x, "batch", None, None)
    return x, positions, mask


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params: Params, batch, cfg: ArchConfig, mesh=None):
    """Full forward pass -> (hidden [B,S,D], aux_loss, positions, mask)."""
    x, positions, mask = _embed(params, batch, cfg)
    aux_total = jnp.float32(0.0)

    if cfg.family in ("dense", "vlm", "audio"):
        windows = _per_layer_window(cfg, cfg.n_layers)

        def layer(carry, xs):
            x = carry
            p = xs["p"]
            win = xs["win"] if windows is not None else (
                cfg.window if cfg.window else None
            )
            a, _, _ = _attn_block(p, x, positions, cfg, win)
            x = x + a
            x = x + _mlp_block(p, x, cfg)
            return x, None

        xs = {"p": params["blocks"]}
        if windows is not None:
            xs["win"] = windows
        x, _ = jax.lax.scan(_maybe_remat(layer, cfg), x, xs)

    elif cfg.family == "moe":
        def dense_layer(carry, p):
            x = carry
            a, _, _ = _attn_block(p, x, positions, cfg, cfg.window)
            x = x + a
            x = x + _mlp_block(p, x, cfg)
            return x, None

        def moe_layer(carry, p):
            x, aux = carry
            a, _, _ = _attn_block(p, x, positions, cfg, cfg.window)
            x = x + a
            m, aux_l = _moe_block(p, x, cfg, mesh)
            return (x + m, aux + aux_l), None

        if cfg.first_dense:
            x, _ = jax.lax.scan(_maybe_remat(dense_layer, cfg), x, params["dense_blocks"])
        (x, aux_total), _ = jax.lax.scan(
            _maybe_remat(moe_layer, cfg), (x, aux_total), params["blocks"]
        )

    elif cfg.family == "ssm":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)

        def layer(carry, p):
            x = carry
            xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
            x = x + ssm_mod.ssm_block(p, xn, dims)
            return x, None

        x, _ = jax.lax.scan(_maybe_remat(layer, cfg), x, params["blocks"])

    elif cfg.family == "hybrid":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)
        n_groups = cfg.n_layers // cfg.attn_every
        shared = params["shared"]

        def ssm_layer(x, p):
            xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
            return x + ssm_mod.ssm_block(p, xn, dims)

        def group(carry, p_group):
            x = carry
            def inner(c, p):
                return ssm_layer(c, p), None
            x, _ = jax.lax.scan(inner, x, p_group)
            a, _, _ = _attn_block(shared, x, positions, cfg, cfg.window)
            x = x + a
            x = x + _mlp_block(shared, x, cfg)
            return x, None

        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, cfg.attn_every, *a.shape[1:]),
            params["blocks"],
        )
        x, _ = jax.lax.scan(_maybe_remat(group, cfg), x, grouped)
        if "tail" in params:
            def tail_layer(c, p):
                return ssm_layer(c, p), None
            x, _ = jax.lax.scan(tail_layer, x, params["tail"])
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return x, aux_total, positions, mask


def train_loss(params: Params, batch, cfg: ArchConfig, mesh=None) -> tuple[jnp.ndarray, dict]:
    h, aux, _, mask = forward(params, batch, cfg, mesh)
    labels = batch["labels"]
    if cfg.prefix_len:  # prefix positions carry no labels
        pad = jnp.zeros((labels.shape[0], cfg.prefix_len), labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    ce = chunked_cross_entropy(h, params["unembed"], labels, mask, vocab=cfg.vocab)
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


# ============================================================== serving ====
def _quantize_token_kv(kv: jnp.ndarray, bits: int):
    """[..., hd] -> (int8 payload, f32 scale[..., 1]) per (token, head).
    bits == 4 bit-packs nibble pairs along hd, so the payload trailing dim is
    hd//2 (matching the int4 page-pool layout)."""
    amax = jnp.maximum(jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1, keepdims=True), 1e-30)
    qmax = float(2 ** (bits - 1) - 1)
    scale = amax / qmax
    q = jnp.clip(jnp.round(kv.astype(jnp.float32) / scale), -qmax - 1, qmax).astype(jnp.int8)
    if bits == 4:
        from repro.quant.pack import pack_int4

        q = pack_int4(q, axis=-1)
    return q, scale.astype(jnp.float32)


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int) -> Params:
    """Pre-allocated decode cache.  KV payloads are int8 when
    cfg.serve_kv_bits < 16 (the paper's multi-precision idea applied to the
    dominant serving memory consumer), bf16 otherwise."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    quant = cfg.serve_kv_bits < 16
    if cfg.serve_kv_bits == 4:
        hd = hd // 2  # nibble-packed payload (the paged serve path unpacks)
    kv_dtype = jnp.int8 if quant else jnp.dtype(cfg.dtype)
    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        n_attn = cfg.n_layers
        cache["k"] = jnp.zeros((n_attn, batch_size, max_len, kv, hd), kv_dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        if quant:
            cache["k_scale"] = jnp.zeros((n_attn, batch_size, max_len, kv, 1), jnp.float32)
            cache["v_scale"] = jnp.zeros_like(cache["k_scale"])
    elif cfg.family == "ssm":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)
        cache["ssm"] = jnp.zeros(
            (cfg.n_layers, batch_size, dims.n_heads, dims.head_p, dims.state), jnp.float32
        )
    elif cfg.family == "hybrid":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)
        n_groups = cfg.n_layers // cfg.attn_every
        rem = cfg.n_layers % cfg.attn_every
        cache["ssm"] = jnp.zeros(
            (n_groups * cfg.attn_every, batch_size, dims.n_heads, dims.head_p, dims.state),
            jnp.float32,
        )
        if rem:
            cache["ssm_tail"] = jnp.zeros(
                (rem, batch_size, dims.n_heads, dims.head_p, dims.state), jnp.float32
            )
        cache["k"] = jnp.zeros((n_groups, batch_size, max_len, kv, hd), kv_dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        if quant:
            cache["k_scale"] = jnp.zeros((n_groups, batch_size, max_len, kv, 1), jnp.float32)
            cache["v_scale"] = jnp.zeros_like(cache["k_scale"])
    return cache


def _write_cache_slab(cache_k, kq, pos):
    """Write [B, S_new, ...] at sequence offset pos into [B, S_max, ...]."""
    return jax.lax.dynamic_update_slice_in_dim(cache_k, kq, pos, axis=1)


def prefill(params: Params, batch, cfg: ArchConfig, max_len: int, mesh=None):
    """Processes the full prompt, returns (last-token logits [B, V], cache)."""
    x, positions, _ = _embed(params, batch, cfg)
    b, s, _ = x.shape
    quant = cfg.serve_kv_bits < 16
    cache = init_cache(cfg, b, max_len)

    def fill_kv(k, v):
        if quant:
            kq, ks = _quantize_token_kv(k, cfg.serve_kv_bits)
            vq, vs = _quantize_token_kv(v, cfg.serve_kv_bits)
            return kq, vq, ks, vs
        return k, v, None, None

    if cfg.family in ("dense", "vlm", "audio"):
        windows = _per_layer_window(cfg, cfg.n_layers)

        def layer(carry, xs):
            x = carry
            p = xs["p"]
            win = xs["win"] if windows is not None else (cfg.window if cfg.window else None)
            a, k, v = _attn_block(p, x, positions, cfg, win)
            x = x + a
            x = x + _mlp_block(p, x, cfg)
            return x, fill_kv(k, v)

        xs = {"p": params["blocks"]}
        if windows is not None:
            xs["win"] = windows
        x, kvs = jax.lax.scan(_maybe_remat(layer, cfg), x, xs)
        kq, vq, ks, vs = kvs
        cache["k"] = cache["k"].at[:, :, :s].set(kq)
        cache["v"] = cache["v"].at[:, :, :s].set(vq)
        if quant:
            cache["k_scale"] = cache["k_scale"].at[:, :, :s].set(ks)
            cache["v_scale"] = cache["v_scale"].at[:, :, :s].set(vs)

    elif cfg.family == "moe":
        def dense_layer(carry, p):
            x = carry
            a, k, v = _attn_block(p, x, positions, cfg, cfg.window)
            x = x + a
            x = x + _mlp_block(p, x, cfg)
            return x, fill_kv(k, v)

        def moe_layer(carry, p):
            x = carry
            a, k, v = _attn_block(p, x, positions, cfg, cfg.window)
            x = x + a
            m, _ = _moe_block(p, x, cfg, mesh)
            return x + m, fill_kv(k, v)

        kv_parts = []
        if cfg.first_dense:
            x, kv0 = jax.lax.scan(_maybe_remat(dense_layer, cfg), x, params["dense_blocks"])
            kv_parts.append(kv0)
        x, kv1 = jax.lax.scan(_maybe_remat(moe_layer, cfg), x, params["blocks"])
        kv_parts.append(kv1)
        kvs = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *kv_parts) if len(kv_parts) > 1 else kv_parts[0]
        kq, vq, ks, vs = kvs
        cache["k"] = cache["k"].at[:, :, :s].set(kq)
        cache["v"] = cache["v"].at[:, :, :s].set(vq)
        if quant:
            cache["k_scale"] = cache["k_scale"].at[:, :, :s].set(ks)
            cache["v_scale"] = cache["v_scale"].at[:, :, :s].set(vs)

    elif cfg.family == "ssm":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)

        def layer(carry, p):
            x = carry
            xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
            y, st = ssm_mod.ssm_block_with_state(p, xn, dims)
            return x + y, st

        x, states = jax.lax.scan(_maybe_remat(layer, cfg), x, params["blocks"])
        cache["ssm"] = states

    elif cfg.family == "hybrid":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)
        n_groups = cfg.n_layers // cfg.attn_every
        shared = params["shared"]

        def group(carry, p_group):
            x = carry
            def inner(c, p):
                xn = rms_norm(c, p["norm1"].astype(c.dtype), cfg.norm_eps)
                y, st = ssm_mod.ssm_block_with_state(p, xn, dims)
                return c + y, st
            x, sts = jax.lax.scan(inner, x, p_group)
            a, k, v = _attn_block(shared, x, positions, cfg, cfg.window)
            x = x + a
            x = x + _mlp_block(shared, x, cfg)
            return x, (sts, fill_kv(k, v))

        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, cfg.attn_every, *a.shape[1:]),
            params["blocks"],
        )
        x, (sts, kvs) = jax.lax.scan(_maybe_remat(group, cfg), x, grouped)
        cache["ssm"] = sts.reshape(n_groups * cfg.attn_every, *sts.shape[2:])
        kq, vq, ks, vs = kvs
        cache["k"] = cache["k"].at[:, :, :s].set(kq)
        cache["v"] = cache["v"].at[:, :, :s].set(vq)
        if quant:
            cache["k_scale"] = cache["k_scale"].at[:, :, :s].set(ks)
            cache["v_scale"] = cache["v_scale"].at[:, :, :s].set(vs)
        if "tail" in params:
            def tail_layer(c, p):
                xn = rms_norm(c, p["norm1"].astype(c.dtype), cfg.norm_eps)
                y, st = ssm_mod.ssm_block_with_state(p, xn, dims)
                return c + y, st
            x, tsts = jax.lax.scan(tail_layer, x, params["tail"])
            cache["ssm_tail"] = tsts

    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = dense(x[:, -1], params["unembed"]).astype(jnp.float32)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab, logits, -1e30)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits, cache


def _decode_attn(p, x, cache_slice, pos, cfg: ArchConfig, window):
    """One-layer decode attention: x [B,1,D] + cache slice -> (out, new kv)."""
    from repro.models.layers import apply_rope

    if cfg.serve_kv_bits == 4:
        raise NotImplementedError(
            "int4 KV payloads are nibble-packed; only the paged serve path "
            "(serve/decode.py) unpacks them — use ServeEngine, not decode_step"
        )
    b = x.shape[0]
    kv, hd, h = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
    q = dense(xn, p["wq"]).reshape(b, 1, h, hd)
    k = dense(xn, p["wk"]).reshape(b, 1, kv, hd)
    v = dense(xn, p["wv"]).reshape(b, 1, kv, hd)
    posv = jnp.broadcast_to(pos[None, None], (b, 1))
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    quant = cfg.serve_kv_bits < 16
    ck, cv = cache_slice["k"], cache_slice["v"]
    if quant:
        kq, ksc = _quantize_token_kv(k, cfg.serve_kv_bits)
        vq, vsc = _quantize_token_kv(v, cfg.serve_kv_bits)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, kq, pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, vq, pos, axis=1)
        cks = jax.lax.dynamic_update_slice_in_dim(cache_slice["k_scale"], ksc, pos, axis=1)
        cvs = jax.lax.dynamic_update_slice_in_dim(cache_slice["v_scale"], vsc, pos, axis=1)
        o = attn_mod.decode_attention(
            q, ck, cv, pos + 1, window=window, k_scale=cks, v_scale=cvs
        )
        new_slice = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, pos, axis=1)
        o = attn_mod.decode_attention(q, ck, cv, pos + 1, window=window)
        new_slice = {"k": ck, "v": cv}
    o = dense(o.reshape(b, 1, h * hd), p["wo"])
    return o, new_slice


def decode_step(params: Params, tokens: jnp.ndarray, cache: Params, cfg: ArchConfig, mesh=None):
    """One decode step: tokens [B, 1] -> (logits [B, V], updated cache)."""
    pos = cache["pos"]
    x = params["embed"].astype(jnp.dtype(cfg.dtype))[tokens]  # [B, 1, D]
    b = x.shape[0]
    quant = cfg.serve_kv_bits < 16
    new_cache = dict(cache)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        windows = _per_layer_window(cfg, cfg.n_layers)

        # Decode scans layer-by-layer; MoE models with a leading dense block
        # run the dense prefix unstacked (first_dense is 0 or 1 in practice).
        def layer(carry, xs):
            x = carry
            p, sl = xs["p"], xs["cache"]
            win = xs["win"] if windows is not None else (cfg.window if cfg.window else None)
            a, new_sl = _decode_attn(p, x, sl, pos, cfg, win)
            x = x + a
            if cfg.family == "moe":
                m, _ = _moe_block(p, x, cfg, mesh)
                x = x + m
            else:
                x = x + _mlp_block(p, x, cfg)
            return x, new_sl

        off = 0
        if cfg.family == "moe" and cfg.first_dense:
            for i in range(cfg.first_dense):
                p_i = jax.tree.map(lambda a: a[i], params["dense_blocks"])
                sl = {k: cache[k][i] for k in ("k", "v") if k in cache}
                if quant:
                    sl |= {k: cache[k][i] for k in ("k_scale", "v_scale")}
                a, new_sl = _decode_attn(p_i, x, sl, pos, cfg, cfg.window)
                x = x + a
                x = x + _mlp_block(p_i, x, cfg)
                for k, v_ in new_sl.items():
                    new_cache[k] = new_cache[k].at[i].set(v_)
            off = cfg.first_dense

        xs = {
            "p": params["blocks"],
            "cache": {k: cache[k][off:] for k in (("k", "v", "k_scale", "v_scale") if quant else ("k", "v"))},
        }
        if windows is not None:
            xs["win"] = windows[off:]
        x, new_slices = jax.lax.scan(layer, x, xs)
        for k, v_ in new_slices.items():
            new_cache[k] = new_cache[k].at[off:].set(v_)

    elif cfg.family == "ssm":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)

        def layer(carry, xs):
            x = carry
            p, st = xs
            xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
            y, st_new = ssm_mod.ssm_decode_step(p, xn, st, dims)
            return x + y, st_new

        x, states = jax.lax.scan(layer, x, (params["blocks"], cache["ssm"]))
        new_cache["ssm"] = states

    elif cfg.family == "hybrid":
        dims = ssm_mod.ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_p)
        n_groups = cfg.n_layers // cfg.attn_every
        shared = params["shared"]
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, cfg.attn_every, *a.shape[1:]),
            params["blocks"],
        )
        ssm_grouped = cache["ssm"].reshape(n_groups, cfg.attn_every, *cache["ssm"].shape[1:])
        cache_keys = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")

        def group(carry, xs):
            x = carry
            p_group, sts, sl = xs

            def inner(c, xs2):
                p, st = xs2
                xn = rms_norm(c, p["norm1"].astype(c.dtype), cfg.norm_eps)
                y, st_new = ssm_mod.ssm_decode_step(p, xn, st, dims)
                return c + y, st_new

            x, sts_new = jax.lax.scan(inner, x, (p_group, sts))
            a, new_sl = _decode_attn(shared, x, sl, pos, cfg, cfg.window)
            x = x + a
            x = x + _mlp_block(shared, x, cfg)
            return x, (sts_new, new_sl)

        sl_stack = {k: cache[k] for k in cache_keys}
        x, (sts_new, new_slices) = jax.lax.scan(group, x, (grouped, ssm_grouped, sl_stack))
        new_cache["ssm"] = sts_new.reshape(cache["ssm"].shape)
        for k, v_ in new_slices.items():
            new_cache[k] = v_
        if "tail" in params:
            def tail_layer(c, xs2):
                p, st = xs2
                xn = rms_norm(c, p["norm1"].astype(c.dtype), cfg.norm_eps)
                y, st_new = ssm_mod.ssm_decode_step(p, xn, st, dims)
                return c + y, st_new
            x, tsts = jax.lax.scan(tail_layer, x, (params["tail"], cache["ssm_tail"]))
            new_cache["ssm_tail"] = tsts

    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = dense(x[:, -1], params["unembed"]).astype(jnp.float32)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab, logits, -1e30)
    new_cache["pos"] = pos + 1
    return logits, new_cache
