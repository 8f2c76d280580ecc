"""Jit'd ragged batched decode step straight over the paged KV pool.

One call decodes one token for every request in a same-precision group.
Attention never materializes a contiguous cache view: each layer calls
``models.attention.paged_decode_attention``, which walks the group's page
tables inside the kernel (Pallas on TPU, slot-scan XLA fallback elsewhere)
and reads only the pages holding each row's ``lengths[b]`` cached tokens.
The token being decoded enters the online softmax as a fused extra term, and
after the layer scan its quantized K/V is scattered *directly* into its page
(``pool.at[:, page, :, off].set``) — the old gather → insert → re-scatter
round-trip through a ``[L, B, S, Hkv, D]`` view is gone, so per-token
attention traffic is proportional to actual cache lengths, not
``L x B x table_capacity``.

All weight matmuls go through ``models.layers.dense``, which dispatches
quantized weights to the ``mpmm`` multi-precision kernel path, so a W4A16
group and a W8A16 group each cost one batched kernel call per projection per
layer.  Unlike ``models.transformer.decode_step`` (one shared scalar
position), every row carries its own cache length — requests that joined the
batch at different times decode together.  Rows with ``valid[b] == False``
are pow2-bucket padding: they compute garbage logits (sliced off by the
engine) and their append is dropped via an out-of-range page id.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import attention as attn_mod
from repro.models import transformer as model_lib
from repro.models.layers import apply_rope, dense, rms_norm
from repro.serve.kv_cache import scatter_tokens


def paged_decode_step(
    params,
    tokens: jnp.ndarray,  # [B, 1] int32 — last generated token per request
    lengths: jnp.ndarray,  # [B] int32 — tokens already in cache (new token's position)
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    valid: jnp.ndarray,  # [B] bool — False for pow2-bucket padding rows
    pool_k: jnp.ndarray,  # [L, P, Hkv, ps, Dk]
    pool_v: jnp.ndarray,
    pool_ks,  # [L, P, Hkv, ps] f32 or None (kv_bits == 16)
    pool_vs,
    *,
    cfg: ArchConfig,
    mesh=None,
):
    """Returns (logits [B, V], new_pools) where new_pools is the page pool
    with every valid row's new token already scattered into its page —
    (k, v, k_scale, v_scale), scales None when kv_bits == 16.  The caller
    adopts the returned pools (donation makes the scatter in-place).

    Append precondition: every row with valid[b] == True must have a table
    slot allocated for position lengths[b] (lengths[b] < table_len * ps —
    the engine guarantees this via _ensure_page_room).  Zero-padded table
    entries are indistinguishable from a real page 0, so a row whose *own*
    table is exhausted inside a wider padded table would scatter into page 0;
    set valid[b] = False for any row that must not append.  Appends at or
    past the padded width W are dropped automatically.

    Not jit'd here: the engine jits a closure over its mesh (mesh objects
    aren't hashable jit statics), mirroring how it wraps prefill."""
    quant = cfg.serve_kv_bits < 16
    b = tokens.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_layers = pool_k.shape[0]
    num_pages, page_size = pool_k.shape[1], pool_k.shape[3]
    x = params["embed"].astype(jnp.dtype(cfg.dtype))[tokens]  # [B, 1, D]
    posv = lengths[:, None]  # [B, 1] per-row positions
    rows = jnp.arange(b)

    windows = model_lib._per_layer_window(cfg, cfg.n_layers)

    def layer(carry, xs):
        x = carry
        p, li = xs["p"], xs["li"]
        win = xs["win"] if windows is not None else (cfg.window if cfg.window else None)
        xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
        q = dense(xn, p["wq"]).reshape(b, 1, h, hd)
        k = dense(xn, p["wk"]).reshape(b, 1, hkv, hd)
        v = dense(xn, p["wv"]).reshape(b, 1, hkv, hd)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
        if quant:
            kq, ksc = model_lib._quantize_token_kv(k[:, 0], cfg.serve_kv_bits)
            vq, vsc = model_lib._quantize_token_kv(v[:, 0], cfg.serve_kv_bits)
            new_kv = (kq, vq, ksc[..., 0], vsc[..., 0])  # scales [B, Hkv]
            o = attn_mod.paged_decode_attention(
                q, pool_k, pool_v, tables, lengths, li, kq, vq,
                window=win, k_scale=pool_ks, v_scale=pool_vs,
                new_k_scale=new_kv[2], new_v_scale=new_kv[3],
                kv_bits=cfg.serve_kv_bits,
            )
        else:
            kc = k[:, 0].astype(pool_k.dtype)
            vc = v[:, 0].astype(pool_v.dtype)
            o = attn_mod.paged_decode_attention(
                q, pool_k, pool_v, tables, lengths, li, kc, vc,
                window=win, kv_bits=cfg.serve_kv_bits,
            )
            new_kv = (kc, vc)
        x = x + dense(o.reshape(b, 1, h * hd), p["wo"])
        if cfg.family == "moe":
            m, _ = model_lib._moe_block(p, x, cfg, mesh)
            x = x + m
        else:
            x = x + model_lib._mlp_block(p, x, cfg)
        return x, new_kv

    xs = {"p": params["blocks"], "li": jnp.arange(n_layers, dtype=jnp.int32)}
    if windows is not None:
        xs["win"] = windows
    x, new_kv = jax.lax.scan(layer, x, xs)

    # Fused token append: scatter each row's new K/V straight into its page.
    # Padding rows get an out-of-range page id, which jax scatters drop; a
    # slot index at/past the padded width W must fill out-of-range too, not
    # clamp to the last entry and overwrite it.  (A row whose own shorter
    # table is exhausted *inside* W is the caller's job to mask via `valid`
    # — see the append precondition in the docstring.)
    page_ids = tables.at[rows, lengths // page_size].get(
        mode="fill", fill_value=num_pages
    )
    page_ids = jnp.where(valid, page_ids, num_pages)
    pools = scatter_tokens(
        (pool_k, pool_v, pool_ks, pool_vs), new_kv, page_ids,
        lengths % page_size, mode="drop",
    )

    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = dense(x[:, -1], params["unembed"]).astype(jnp.float32)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab, logits, -1e30)
    return logits, pools


def paged_decode_sample(
    params,
    tokens: jnp.ndarray,  # [B, 1] int32 — last generated token per request
    lengths: jnp.ndarray,  # [B] int32 — tokens already in cache
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    valid: jnp.ndarray,  # [B] bool — False for pow2-bucket padding rows
    samp,  # (temperature [B], top_k [B], top_p [B], seed [B], position [B])
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    pool_ks,
    pool_vs,
    *,
    cfg: ArchConfig,
    mesh=None,
):
    """One decode step *and* the next-token choice, fused in one jitted
    graph: runs :func:`paged_decode_step`, then draws each row's next token
    with its own (temperature, top_k, top_p) under the position-keyed PRNG
    (``kernels/ops.py::sample_tokens``; greedy rows are exact argmax).
    ``samp is None`` means the whole group is greedy — the graph is the bare
    argmax, identical to the pre-sampling engine, paying zero sampling
    compute; ``top_k``/``top_p`` may likewise be None inside the tuple when
    no row in the group uses them (the mask sorts are elided statically).
    Returns (next_tokens [B] int32, new_pools)."""
    logits, pools = paged_decode_step(
        params, tokens, lengths, tables, valid,
        pool_k, pool_v, pool_ks, pool_vs, cfg=cfg, mesh=mesh,
    )
    if samp is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools
    temps, top_ks, top_ps, seeds, positions = samp
    keys = ops.sample_keys(seeds, positions)
    return ops.sample_tokens(logits, keys, temps, top_ks, top_ps), pools
