"""Jit'd chunked-prefill step straight over the paged KV pool.

One call advances a same-precision group of requests through one chunk of
their (uncached) prompt suffix.  Chunk token ``i`` of row ``b`` sits at
absolute position ``q_start[b] + i``; attention reads the pages holding each
row's ``q_start[b]`` already-materialized tokens — the prefix-cache hit plus
earlier chunks — through the page tables inside the kernel
(``models.attention.paged_prefill_attention``), and the chunk attends to
itself causally as a fused term, so no contiguous cache view ever
materializes and no cached token is recomputed.  After the layer scan the
chunk's (quantized) K/V is scattered straight into its pages, exactly like
``serve/decode.py`` scatters a decoded token.

This one function serves both prefill shapes the engine needs:

* **cold bucketed group prefill** — mixed-length admissions padded to one
  pow2 token bucket (``q_lens[b] <= C`` masks the ragged tails) prefill as a
  single call instead of one call per distinct prompt length;
* **warm / long chunked prefill** — a request with a prefix-cache hit (or a
  prompt longer than the chunk budget) advances ``C`` tokens per engine
  step, interleaved with running decodes, with ``q_start`` picking up where
  the cache (or the previous chunk) stopped.

Returns per-row logits at each row's *last valid* chunk position, so the
call that completes a prompt yields the request's first output token.
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


def bucket_pow2(n: int) -> int:
    """Smallest power of two >= n (jit-shape bucketing)."""
    return 1 << max(n - 1, 0).bit_length()


def chunk_forward(
    params,
    tokens: jnp.ndarray,  # [B, C] int32 — this chunk's tokens (tail-padded)
    q_start: jnp.ndarray,  # [B] int32 — tokens already materialized per row
    q_lens: jnp.ndarray,  # [B] int32 — valid tokens of this chunk (<= C)
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    pool_k: jnp.ndarray,  # [L, P, Hkv, ps, Dk]
    pool_v: jnp.ndarray,
    pool_ks,  # [L, P, Hkv, ps] f32 or None (kv_bits == 16)
    pool_vs,
    *,
    cfg: ArchConfig,
    mesh=None,
    verify: bool = False,
):
    """Run one causal self-chunk through the model: returns (final-normed
    hidden states [B, C, D], new_pools) with the chunk's K/V already
    scattered into its pages — (k, v, k_scale, v_scale), scales None when
    kv_bits == 16.  The caller adopts the returned pools (donation makes the
    scatter in-place).

    This is the shared forward of both chunked prefill
    (:func:`chunk_prefill_step`, which only needs the last valid position's
    logits) and speculative verify (serve/spec_decode.py, which needs every
    window position's logits) — a verify window *is* a causal self-chunk.
    ``verify`` picks the attention entry point
    (``paged_verify_attention`` vs ``paged_prefill_attention``; identical
    kernel contract, separate dispatch for profiling/stats).

    Preconditions: every row's table covers positions ``[0, q_start + q_len)``
    (the engine allocates the full prompt's pages at admission, forking any
    shared page the suffix writes into), and positions ``[0, q_start)`` are
    already materialized in the pool.  Padding positions (``i >= q_lens[b]``)
    never scatter.  Not jit'd here: the engine jits a closure over its mesh,
    mirroring decode."""
    attn_fn = (
        attn_mod.paged_verify_attention if verify
        else attn_mod.paged_prefill_attention
    )
    quant = cfg.serve_kv_bits < 16
    b, c = tokens.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_layers = pool_k.shape[0]
    num_pages, page_size = pool_k.shape[1], pool_k.shape[3]
    x = params["embed"].astype(jnp.dtype(cfg.dtype))[tokens]  # [B, C, D]
    q_start = q_start.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    cpos = jnp.arange(c, dtype=jnp.int32)
    posv = q_start[:, None] + cpos[None, :]  # [B, C] absolute positions
    rows = jnp.arange(b)

    windows = model_lib._per_layer_window(cfg, cfg.n_layers)

    def layer(carry, xs):
        x = carry
        p, li = xs["p"], xs["li"]
        win = xs["win"] if windows is not None else (cfg.window if cfg.window else None)
        xn = rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
        q = dense(xn, p["wq"]).reshape(b, c, h, hd)
        k = dense(xn, p["wk"]).reshape(b, c, hkv, hd)
        v = dense(xn, p["wv"]).reshape(b, c, hkv, hd)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
        if quant:
            kq, ksc = model_lib._quantize_token_kv(k, cfg.serve_kv_bits)
            vq, vsc = model_lib._quantize_token_kv(v, cfg.serve_kv_bits)
            new_kv = (kq, vq, ksc[..., 0], vsc[..., 0])  # scales [B, C, Hkv]
            o = attn_fn(
                q, pool_k, pool_v, tables, q_start, q_lens, li, kq, vq,
                window=win, k_scale=pool_ks, v_scale=pool_vs,
                chunk_k_scale=new_kv[2], chunk_v_scale=new_kv[3],
                kv_bits=cfg.serve_kv_bits,
            )
        else:
            kc = k.astype(pool_k.dtype)
            vc = v.astype(pool_v.dtype)
            o = attn_fn(
                q, pool_k, pool_v, tables, q_start, q_lens, li, kc, vc,
                window=win, kv_bits=cfg.serve_kv_bits,
            )
            new_kv = (kc, vc)
        x = x + dense(o.reshape(b, c, h * hd), p["wo"])
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

    # Scatter the chunk into its pages: position q_start + i lands in table
    # slot (q_start + i) // ps at offset % ps.  Padding positions (and any
    # slot index at/past the padded table width W) get an out-of-range page
    # id, which jax scatters drop.
    page_ids = tables.at[rows[:, None], posv // page_size].get(
        mode="fill", fill_value=num_pages
    )  # [B, C]
    page_ids = jnp.where(cpos[None, :] < q_lens[:, None], page_ids, num_pages)
    pools = scatter_tokens(
        (pool_k, pool_v, pool_ks, pool_vs), new_kv, page_ids,
        posv % page_size, mode="drop",
    )

    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return x, pools


def chunk_prefill_step(
    params,
    tokens: jnp.ndarray,  # [B, C] int32 — this chunk's tokens (tail-padded)
    q_start: jnp.ndarray,  # [B] int32 — tokens already materialized per row
    q_lens: jnp.ndarray,  # [B] int32 — valid tokens of this chunk (<= C)
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    pool_k: jnp.ndarray,  # [L, P, Hkv, ps, Dk]
    pool_v: jnp.ndarray,
    pool_ks,  # [L, P, Hkv, ps] f32 or None (kv_bits == 16)
    pool_vs,
    *,
    cfg: ArchConfig,
    mesh=None,
):
    """Returns (logits [B, V] at each row's last valid chunk position,
    new_pools); see :func:`chunk_forward` for the contract."""
    x, pools = chunk_forward(
        params, tokens, q_start, q_lens, tables,
        pool_k, pool_v, pool_ks, pool_vs, cfg=cfg, mesh=mesh,
    )
    rows = jnp.arange(x.shape[0])
    last = x[rows, jnp.maximum(q_lens.astype(jnp.int32) - 1, 0)]  # [B, D]
    logits = dense(last, params["unembed"]).astype(jnp.float32)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab, logits, -1e30)
    return logits, pools


def chunk_prefill_sample(
    params,
    tokens: jnp.ndarray,  # [B, C] int32 — this chunk's tokens (tail-padded)
    q_start: jnp.ndarray,  # [B] int32 — tokens already materialized per row
    q_lens: jnp.ndarray,  # [B] int32 — valid tokens of this chunk (<= C)
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    samp,  # (temperature [B], top_k [B], top_p [B], seed [B], position [B])
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    pool_ks,
    pool_vs,
    *,
    cfg: ArchConfig,
    mesh=None,
):
    """One prefill chunk *and* the first-token choice, fused in one jitted
    graph: :func:`chunk_prefill_step` for the logits at each row's last
    valid position, then a per-row position-keyed draw
    (``kernels/ops.py::sample_tokens``; greedy rows are exact argmax).  Only
    rows whose prompt completes this chunk use their token — the engine
    discards the rest.  ``samp is None`` (all-greedy group) compiles to the
    bare argmax graph; None ``top_k``/``top_p`` entries elide the mask sorts
    statically.  Returns (first_tokens [B] int32, new_pools)."""
    logits, pools = chunk_prefill_step(
        params, tokens, q_start, q_lens, tables,
        pool_k, pool_v, pool_ks, pool_vs, cfg=cfg, mesh=mesh,
    )
    if samp is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools
    temps, top_ks, top_ps, seeds, positions = samp
    keys = ops.sample_keys(seeds, positions)
    return ops.sample_tokens(logits, keys, temps, top_ks, top_ps), pools
