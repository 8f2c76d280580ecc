"""Pure-jnp oracles for every Pallas kernel (no pallas imports).

Each oracle defines the exact semantics its kernel must reproduce; the tests
sweep shapes/dtypes/precisions and assert allclose (bit-exact for the integer
paths) against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant.pack import unpack_int4

__all__ = [
    "gather_pages",
    "mpmm_ref",
    "mpconv_ref",
    "mqa_decode_ref",
    "paged_mqa_decode_ref",
    "paged_mqa_prefill_ref",
]


def gather_pages(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """[L, P, Hkv, ps, ...] head-major paged pool + [B, W] page tables ->
    [L, B, W*ps, Hkv, ...] contiguous token-major cache rows (payloads carry
    a trailing Dk, scales none).

    The gather oracle for the paged layout: the kernels index the pool in
    place, so nothing on a hot path materializes this view — tests and
    benchmarks use it to compare paged attention against the dense-cache
    oracles above.
    """
    g = jnp.moveaxis(pool[:, tables], 3, 4)  # [L, B, W, ps, Hkv, ...]
    l, b, w, ps = g.shape[:4]
    return g.reshape(l, b, w * ps, *g.shape[4:])


def mpmm_ref(
    x: jnp.ndarray,
    w_data: jnp.ndarray,
    w_scale: jnp.ndarray,
    *,
    w_bits: int,
    mode: str = "dequant",
) -> jnp.ndarray:
    """Oracle for kernels/mpmm.py.

    int mode: exact int32 (wraparound mod 2^32, like the 32-bit SAU
    accumulators) WITHOUT scaling — the wrapper scales.
    dequant mode: float matmul of x against dequantized weights, f32 accum,
    per-column scale applied.
    """
    w = unpack_int4(w_data, axis=0) if w_bits == 4 else w_data
    if mode == "int":
        # int32 accumulation: wraparound mod 2^32, exactly the kernel's (and
        # the 32-bit SAU accumulator's) semantics.
        acc = jax.lax.dot_general(
            x.astype(jnp.int32),
            w.astype(jnp.int32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc
    acc = jax.lax.dot_general(
        x,
        w.astype(x.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (acc * w_scale.astype(jnp.float32)).astype(x.dtype)


def mpconv_ref(
    x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1, padding: int = 0
) -> jnp.ndarray:
    """NHWC x HWIO integer/float conv oracle (lax.conv in f32/int32)."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        out = jax.lax.conv_general_dilated(
            x.astype(jnp.int32),
            w.astype(jnp.int32),
            (stride, stride),
            [(padding, padding), (padding, padding)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.int32,
        )
        return out
    return jax.lax.conv_general_dilated(
        x,
        w.astype(x.dtype),
        (stride, stride),
        [(padding, padding), (padding, padding)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def mqa_decode_ref(
    q: jnp.ndarray,
    k_data: jnp.ndarray,
    v_data: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_scale: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    sm_scale: float,
) -> jnp.ndarray:
    """Oracle for kernels/mqa_decode.py — single-token GQA attention over a
    quantized KV cache.

    q:        [B, H, D]            (bf16/f32)
    k_data:   [B, S, Hkv, D] int8  (quantized keys)
    v_data:   [B, S, Hkv, D] int8
    k_scale:  [B, S, Hkv, 1] f32   (per-token-per-head scales)
    v_scale:  [B, S, Hkv, 1] f32
    lengths:  [B] int32 — valid cache length per sequence (masking)
    returns:  [B, H, D] in q.dtype
    """
    b, h, d = q.shape
    s, hkv = k_data.shape[1], k_data.shape[2]
    groups = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, groups, d)
    kf = k_data.astype(jnp.float32) * k_scale.astype(jnp.float32)
    vf = v_data.astype(jnp.float32) * v_scale.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf) * sm_scale
    mask = jnp.arange(s)[None, :] < lengths[:, None]  # [B, S]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    return out.reshape(b, h, d).astype(q.dtype)


def paged_mqa_decode_ref(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    k_scale,
    v_scale,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    layer,
    new_k: jnp.ndarray,
    new_v: jnp.ndarray,
    new_k_scale=None,
    new_v_scale=None,
    *,
    sm_scale: float,
    window=None,
) -> jnp.ndarray:
    """Oracle for kernels/paged_decode.py — single-token GQA attention over a
    *paged* quantized KV pool, plus the step's own (not-yet-stored) token.

    q:        [B, H, D]
    k_pool:   [L, P, Hkv, ps, D]  int8 payload (pre-unpacked for kv4) or float
    k_scale:  [L, P, Hkv, ps]     f32, or None for 16-bit pools
    tables:   [B, W] int32 — page ids, zero-padded past each row's table
    lengths:  [B] int32 — tokens already in the cache; the new token attends
              at position lengths[b], so the softmax spans lengths[b] + 1
              positions (never empty, even at lengths == 0)
    layer:    which pool layer to read
    new_k:    [B, Hkv, D] payload of this step's token (same dtype as pool)
    new_k_scale: [B, Hkv] f32, or None
    returns:  [B, H, D] in q.dtype

    Semantics are gather-based on purpose: pages are collected into the
    contiguous [B, W*ps, ...] view, the new token is inserted at its own
    position, and plain masked softmax runs over it — the exact computation
    the old serve path performed, kept as the bit-reference for the kernel.
    """
    b, h, d = q.shape
    hkv, ps = k_pool.shape[2], k_pool.shape[3]
    w = tables.shape[1]
    s = w * ps
    rows = jnp.arange(b)
    lengths = lengths.astype(jnp.int32)

    def gather(pool, scale, new, new_scale):
        g = gather_pages(pool[layer][None], tables)[0]  # [B, S, Hkv, D]
        g = g.astype(jnp.float32)
        if scale is not None:
            sc = gather_pages(scale[layer][None], tables)[0]  # [B, S, Hkv]
            g = g * sc.astype(jnp.float32)[..., None]
        nf = new.astype(jnp.float32)
        if new_scale is not None:
            nf = nf * new_scale.astype(jnp.float32)[..., None]
        # one spare position so a full table (lengths == W*ps) still has
        # room for this step's token
        g = jnp.pad(g, ((0, 0), (0, 1)) + ((0, 0),) * (g.ndim - 2))
        return g.at[rows, lengths].set(nf)

    kf = gather(k_pool, k_scale, new_k, new_k_scale)
    vf = gather(v_pool, v_scale, new_v, new_v_scale)
    s = s + 1
    total = lengths + 1  # cache + this step's token
    qf = q.astype(jnp.float32).reshape(b, hkv, h // hkv, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf) * sm_scale
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    mask = pos < total[:, None]
    if window is not None:
        mask = mask & (pos >= total[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    return out.reshape(b, h, d).astype(q.dtype)


def paged_mqa_prefill_ref(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    k_scale,
    v_scale,
    tables: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    q_lens: jnp.ndarray,
    layer,
    chunk_k: jnp.ndarray,
    chunk_v: jnp.ndarray,
    chunk_k_scale=None,
    chunk_v_scale=None,
    *,
    sm_scale: float,
    window=None,
) -> jnp.ndarray:
    """Oracle for kernels/paged_prefill.py — a C-token query chunk attending
    to a *paged* quantized KV pool plus the chunk's own (not-yet-stored) K/V.

    q:        [B, C, H, D]
    k_pool:   [L, P, Hkv, ps, D]  int8 payload (pre-unpacked for kv4) or float
    k_scale:  [L, P, Hkv, ps]     f32, or None for 16-bit pools
    tables:   [B, W] int32 — page ids, zero-padded past each row's table
    ctx_lens: [B] int32 — tokens already materialized; chunk token c sits at
              absolute position ctx_lens[b] + c
    q_lens:   [B] int32 — valid chunk tokens per row (<= C; rest is padding
              whose output rows are unspecified garbage)
    chunk_k:  [B, C, Hkv, D] payload of this chunk (same dtype as pool)
    chunk_k_scale: [B, C, Hkv] f32, or None
    returns:  [B, C, H, D] in q.dtype

    Semantics are gather-based on purpose: pages are collected into the
    contiguous [B, W*ps, ...] view, the chunk K/V is appended as extra keys
    at positions ctx + j, and one plain masked softmax runs over both — the
    computation chunked prefill must reproduce without the gather.
    """
    b, c, h, d = q.shape
    hkv, ps = k_pool.shape[2], k_pool.shape[3]
    w = tables.shape[1]
    s = w * ps
    ctx_lens = ctx_lens.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)

    def gather(pool, scale, new, new_scale):
        g = gather_pages(pool[layer][None], tables)[0]  # [B, S, Hkv, D]
        g = g.astype(jnp.float32)
        if scale is not None:
            sc = gather_pages(scale[layer][None], tables)[0]  # [B, S, Hkv]
            g = g * sc.astype(jnp.float32)[..., None]
        nf = new.astype(jnp.float32)
        if new_scale is not None:
            nf = nf * new_scale.astype(jnp.float32)[..., None]
        return jnp.concatenate([g, nf], axis=1)  # [B, S + C, Hkv, D]

    kf = gather(k_pool, k_scale, chunk_k, chunk_k_scale)
    vf = gather(v_pool, v_scale, chunk_v, chunk_v_scale)
    cpos = jnp.arange(c, dtype=jnp.int32)
    # absolute position of every key: pool slots then chunk slots
    k_pos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s)),
         ctx_lens[:, None] + cpos[None, :]], axis=1,
    )  # [B, S + C]
    k_valid = jnp.concatenate(
        [jnp.arange(s, dtype=jnp.int32)[None, :] < ctx_lens[:, None],
         jnp.broadcast_to(cpos[None, :] < q_lens[:, None], (b, c))], axis=1,
    )
    q_pos = ctx_lens[:, None] + cpos[None, :]  # [B, C]
    mask = k_valid[:, None, :] & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask = mask & (q_pos[:, :, None] - k_pos[:, None, :] < window)
    qf = q.astype(jnp.float32).reshape(b, c, hkv, h // hkv, d)
    scores = jnp.einsum("bckgd,bskd->bkgcs", qf, kf) * sm_scale
    scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked padding rows
    out = jnp.einsum("bkgcs,bskd->bkgcd", p, vf)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, d).astype(q.dtype)
