"""Attention: blockwise-flash for train/prefill, cache attention for decode.

Train/prefill use a pure-JAX flash attention (online softmax over KV blocks
inside lax.scan) so the [S, S] score matrix never materializes — mandatory at
32k+ context and the standard TPU-native formulation (the Pallas analogue on
a real TPU pod swaps in transparently; the dry-run/roofline path needs the
scan form so XLA's SPMD partitioner can reason about it).

Decode attends one query token against the (optionally int8/int4-quantized)
KV cache; sequence-sharded caches reduce via XLA-inserted collectives
(flash-decoding style partial-softmax combine is exposed to the partitioner
through einsum + softmax over the sharded axis).  The Pallas serving kernel
(kernels/mqa_decode.py) implements the same contract for real-TPU serving.

Supports GQA (n_kv_heads < n_heads) and sliding-window masking (gemma3 5:1
local:global, mixtral SWA).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

BLOCK_Q = 512
BLOCK_K = 512
_NEG = -1e30


def _expand_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """[B, S, Hkv, D] -> [B, S, Hkv*groups, D] by repeat (GQA share)."""
    b, s, hkv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, groups, d)).reshape(
        b, s, hkv * groups, d
    )


def flash_attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, Hkv, D]
    v: jnp.ndarray,  # [B, Sk, Hkv, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,  # sliding-window size (None = global)
    q_offset: int = 0,  # absolute position of q[0] (prefill continuation)
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    groups = h // hkv
    sm = 1.0 / jnp.sqrt(jnp.float32(d))
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    # pad to block multiples
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // bq, k.shape[1] // bk

    kg = _expand_kv(k, groups)  # [B, Sk', H, D]
    vg = _expand_kv(v, groups)
    qb = q.reshape(b, nq, bq, h, d).astype(jnp.float32)
    kb = kg.reshape(b, nk, bk, h, d).swapaxes(0, 1)  # [nk, B, bk, H, D]
    vb = vg.reshape(b, nk, bk, h, d).swapaxes(0, 1)

    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (nq, bq), 0) * bq + jax.lax.broadcasted_iota(jnp.int32, (nq, bq), 1)  # [nq, bq]

    def kv_step(carry, xs):
        m, l, acc = carry  # [B, nq, bq, H], same, [B, nq, bq, H, D]
        kc, vc, kidx = xs  # [B, bk, H, D], [B, bk, H, D], scalar
        scores = jnp.einsum("bnqhd,bkhd->bnqhk", qb, kc) * sm  # [B,nq,bq,H,bk]
        k_pos = kidx * bk + jnp.arange(bk, dtype=jnp.int32)  # [bk]
        valid = k_pos[None, None, :] < sk  # mask padded tail
        mask = valid
        if causal:
            mask = mask & (k_pos[None, None, :] <= q_pos[:, :, None])
        if window is not None:
            mask = mask & (q_pos[:, :, None] - k_pos[None, None, :] < window)
        mask_b = mask[None, :, :, None, :]  # [1, nq, bq, 1, bk]
        scores = jnp.where(mask_b, scores, _NEG)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(mask_b, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bnqhk,bkhd->bnqhd", p, vc)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, nq, bq, h), _NEG, jnp.float32)
    l0 = jnp.zeros((b, nq, bq, h), jnp.float32)
    a0 = jnp.zeros((b, nq, bq, h, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        kv_step, (m0, l0, a0), (kb, vb, jnp.arange(nk, dtype=jnp.int32))
    )
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out = out.reshape(b, nq * bq, h, d)[:, :sq]
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_cache: jnp.ndarray,  # [B, S, Hkv, D]  (bf16, or int8 payload)
    v_cache: jnp.ndarray,
    length: jnp.ndarray,  # [B] or scalar: current cache fill
    *,
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [B, S, Hkv, 1] when quantized
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """One-token attention over the cache.  O(S) memory: scores are [B, H, S].

    With a sequence-sharded cache the einsum/softmax below partition to the
    flash-decoding pattern (partial max/denominator + collective combine) —
    XLA SPMD inserts the reductions over the sharded S axis.
    """
    b, _, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    groups = h // hkv
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale.astype(jnp.float32)
    qf = q.reshape(b, hkv, groups, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf) / jnp.sqrt(jnp.float32(d))
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]  # [1, S]
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (b,))
    mask = pos < length[:, None]
    if window is not None:
        mask = mask & (pos >= (length[:, None] - window))
    scores = jnp.where(mask[:, None, None, :], scores, _NEG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, Dk]  (int8 payload or bf16)
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    lengths: jnp.ndarray,  # [B] int32 — tokens already in the cache
    layer,  # int32 — which pool layer this block attends against
    new_k: jnp.ndarray,  # [B, Hkv, Dk] this step's K/V (not yet in the pool)
    new_v: jnp.ndarray,
    *,
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [L, P, Hkv, ps] when quantized
    v_scale: Optional[jnp.ndarray] = None,
    new_k_scale: Optional[jnp.ndarray] = None,  # [B, Hkv]
    new_v_scale: Optional[jnp.ndarray] = None,
    kv_bits: int = 16,
) -> jnp.ndarray:
    """One-token attention straight against the paged KV pool.

    The paged contract of :func:`decode_attention`: instead of a gathered
    contiguous [B, S, Hkv, D] cache view, the kernel walks each row's page
    table and reads only the pages holding its ``lengths[b]`` cached tokens;
    the token being decoded enters the online softmax as an extra term
    (every token attends to itself) so the softmax spans ``lengths + 1``
    positions.  Dispatches to the Pallas kernel on TPU and its slot-scan XLA
    fallback elsewhere (kernels/ops.py::paged_mqa_decode).
    """
    from repro.kernels import ops

    b, _, h, d = q.shape
    out = ops.paged_mqa_decode(
        q.reshape(b, h, d),
        k_pool,
        v_pool,
        k_scale,
        v_scale,
        tables,
        lengths,
        layer,
        new_k,
        new_v,
        new_k_scale,
        new_v_scale,
        kv_bits=kv_bits,
        window=window,
    )
    return out.reshape(b, 1, h, d)


def paged_prefill_attention(
    q: jnp.ndarray,  # [B, C, H, D] — a chunk of C query tokens
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, Dk]  (int8 payload or bf16)
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    ctx_lens: jnp.ndarray,  # [B] int32 — tokens already in the pool
    q_lens: jnp.ndarray,  # [B] int32 — valid chunk tokens per row (<= C)
    layer,  # int32 — which pool layer this block attends against
    chunk_k: jnp.ndarray,  # [B, C, Hkv, Dk] this chunk's K/V (not yet pooled)
    chunk_v: jnp.ndarray,
    *,
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [L, P, Hkv, ps] when quantized
    v_scale: Optional[jnp.ndarray] = None,
    chunk_k_scale: Optional[jnp.ndarray] = None,  # [B, C, Hkv]
    chunk_v_scale: Optional[jnp.ndarray] = None,
    kv_bits: int = 16,
) -> jnp.ndarray:
    """Chunked-prefill attention straight against the paged KV pool.

    The chunk analogue of :func:`paged_decode_attention`: chunk token c sits
    at absolute position ``ctx_lens[b] + c``, attends to every pooled token
    before it through the page tables plus the chunk itself causally, and the
    caller scatters the chunk's K/V into its pages afterwards.  Rows whose
    chunk is bucket-padded set ``q_lens[b] < C``; padded rows produce
    garbage outputs the caller slices off.  Dispatches to the Pallas kernel
    on TPU and its slot-scan XLA fallback elsewhere
    (kernels/ops.py::paged_mqa_prefill)."""
    from repro.kernels import ops

    return ops.paged_mqa_prefill(
        q,
        k_pool,
        v_pool,
        k_scale,
        v_scale,
        tables,
        ctx_lens,
        q_lens,
        layer,
        chunk_k,
        chunk_v,
        chunk_k_scale,
        chunk_v_scale,
        kv_bits=kv_bits,
        window=window,
    )


def paged_verify_attention(*args, **kwargs) -> jnp.ndarray:
    """Speculative-verify attention: a verify window (the last emitted
    token + the draft tokens, ``q_lens = n_draft + 1``) *is* a causal
    self-chunk, so this is :func:`paged_prefill_attention` under a second
    name — the verify entry point stays visible in profiles and docs
    (``kernels/ops.py::paged_mqa_verify`` documents the kernel-level
    contract) without duplicating the 15-parameter plumbing."""
    return paged_prefill_attention(*args, **kwargs)
