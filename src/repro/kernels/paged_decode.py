"""Paged flash-decode GQA attention: index the KV page pool in place.

The serving engine stores every request's KV cache as fixed-size pages in one
shared pool (``serve/kv_cache.py``), head-major so one (page, kv head) pair
is a contiguous ``[ps, Dk]`` tile:

    payload: [L, P, Hkv, ps, Dk]     scales: [L, P, Hkv, ps] f32

A request's *page table* lists its pages in order.  This kernel reads the
pool through the page table, PagedAttention-style:

* grid ``(batch, kv_head, page_slot)`` with the slot dimension innermost and
  sequential; the block-spec index map translates ``(row, slot) -> page_id``
  via a scalar-prefetched table, so each grid step DMAs exactly one
  ``[ps, Dk]`` page tile (its two minor dimensions are whole array
  dimensions, which is what the TPU block-shape rule asks).
* slots at or beyond a row's occupied length are *clamped* to the row's last
  live page: consecutive grid steps then ask for the same block and Pallas
  elides the re-fetch — dead slots cost neither DMA nor (via ``pl.when``)
  compute.  Per-token attention traffic is proportional to the row's actual
  cache length, not the table capacity.
* int8/int4 payloads are dequantized in-register.  Per-(token, head) scales
  arrive as a ``[Hkv, ps]`` block per page; the kernel picks its head's row
  and applies it to the ``[G, ps]`` scores and probabilities (a row of
  scales over the key axis) rather than to the ``[ps, Dk]`` tile.  int4
  bytes hold head-dim pairs ``(2j, 2j+1)`` as (low, high) nibbles; the kernel
  never re-interleaves them — queries and outputs travel as (even, odd)
  head-dim planes, so ``q . k = q_even . k_lo + q_odd . k_hi``.  bf16 pools
  skip the scales.
* the *new* token's K/V (computed this step, not yet in the pool) enters the
  online softmax as one extra term in the final grid step, dequantized to
  f32 by the wrapper, so the caller never round-trips it through a gathered
  view — it scatters the quantized payload straight into its page afterwards.

``paged_mqa_decode_xla`` is the XLA fallback for CPU/interpret runs: a
``lax.scan`` over page slots that gathers one page per live slot through the
table (``lax.cond`` skips slots beyond the longest row), with the same online
softmax and fused new-token term.  Oracle: ``kernels/ref.py::
paged_mqa_decode_ref``;  dispatch: ``kernels/ops.py::paged_mqa_decode``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.pack import int4_nibbles, unpack_int4

__all__ = [
    "paged_mqa_decode_pallas",
    "paged_mqa_decode_xla",
    "dequant_kv",
    "to_planes",
    "from_planes",
    "payload_planes",
]

_NEG_INF = -1e30


# ------------------------------------------------- shared by both paged kernels
def dequant_kv(payload, scale, kv_bits: int) -> jnp.ndarray:
    """[..., Dk] payload + [...] scale -> [..., D] f32 (XLA side)."""
    if kv_bits == 4:
        payload = unpack_int4(payload, axis=-1)
    out = payload.astype(jnp.float32)
    if scale is not None:
        out = out * scale.astype(jnp.float32)[..., None]
    return out


def to_planes(x: jnp.ndarray, kv_bits: int, axis: int) -> jnp.ndarray:
    """[..., D] -> [..., n_pl, ..., D/n_pl] with the plane axis inserted at
    ``axis``: for kv4 the (even, odd) head-dim planes matching the pool's
    (low, high) nibbles, otherwise one plane holding ``x`` as is."""
    n_pl = 2 if kv_bits == 4 else 1
    x = x.reshape(*x.shape[:-1], x.shape[-1] // n_pl, n_pl)
    return jnp.moveaxis(x, -1, axis)


def from_planes(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inverse of :func:`to_planes`."""
    x = jnp.moveaxis(x, axis, -1)
    return x.reshape(*x.shape[:-2], -1)


def payload_planes(tile, kv_bits: int) -> tuple:
    """In-kernel: one ``[n, Dk]`` payload tile -> its f32 head-dim planes
    (the (low, high) nibbles for kv4, the tile itself otherwise)."""
    if kv_bits == 4:
        lo, hi = int4_nibbles(tile)
        return lo.astype(jnp.float32), hi.astype(jnp.float32)
    return (tile.astype(jnp.float32),)


def dot_nt(a, b):
    """a [M, K] @ b[N, K]^T -> [M, N] f32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def dot_nn(a, b):
    """a [M, K] @ b[K, N] -> [M, N] f32."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _paged_kernel(
    # scalar prefetch
    tables_ref,  # [B, W] int32
    lengths_ref,  # [B] int32
    win_lo_ref,  # [B] int32 — first in-window position (0 when no window)
    layer_ref,  # [1] int32
    # blocks
    q_ref,  # [1, 1, n_pl, G, Dk] f32
    k_ref,  # [1, 1, 1, ps, Dk]   (one page of one kv head)
    v_ref,
    *rest,  # [ks_ref, vs_ref,] nk_ref, nv_ref, o_ref + scratch
    ps: int,
    kv_bits: int,
    sm_scale: float,
    n_w: int,
    has_window: bool,
):
    quant = kv_bits < 16
    if quant:
        ks_ref, vs_ref, nk_ref, nv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        nk_ref, nv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    n_pl = q_ref.shape[2]

    b_idx = pl.program_id(0)
    h_idx = pl.program_id(1)
    w_idx = pl.program_id(2)
    length = lengths_ref[b_idx]  # cache tokens; new token sits at `length`

    @pl.when(w_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = [q_ref[0, 0, i] for i in range(n_pl)]  # n_pl x [G, Dk]

    # window lower bound over the total (cache + new token) length
    lo = win_lo_ref[b_idx]
    block_live = w_idx * ps < length
    if has_window:
        block_live = block_live & ((w_idx + 1) * ps > lo)

    @pl.when(block_live)
    def _update():
        kp = payload_planes(k_ref[0, 0, 0], kv_bits)  # n_pl x [ps, Dk]
        vp = payload_planes(v_ref[0, 0, 0], kv_bits)
        scores = dot_nt(q[0], kp[0])  # [G, ps]
        for i in range(1, n_pl):
            scores = scores + dot_nt(q[i], kp[i])
        if quant:
            scores = scores * ks_ref[0, 0, pl.ds(h_idx, 1), :]  # [1, ps] row
        scores = scores * sm_scale
        pos = w_idx * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        valid = pos < length
        if has_window:
            valid = valid & (pos >= lo)
        scores = jnp.where(valid, scores, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        p = jnp.where(valid, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * vs_ref[0, 0, pl.ds(h_idx, 1), :]
        for i in range(n_pl):
            acc_ref[i] = acc_ref[i] * alpha + dot_nn(p, vp[i])
        m_ref[...] = m_new

    @pl.when(w_idx == n_w - 1)
    def _finish():
        # fused new-token term: the token produced this step attends to itself
        # (always inside any window — distance 0) without touching the pool.
        nk = nk_ref[0, 0]  # [n_pl, Dk] f32, dequantized
        nv = nv_ref[0, 0]
        s_new = jnp.sum(q[0] * nk[0:1], axis=1, keepdims=True)  # [G, 1]
        for i in range(1, n_pl):
            s_new = s_new + jnp.sum(q[i] * nk[i : i + 1], axis=1, keepdims=True)
        s_new = s_new * sm_scale
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s_new)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_new - m_new)  # [G, 1]
        denom = jnp.maximum(l_ref[...] * alpha + p, 1e-20)
        for i in range(n_pl):
            o_ref[0, 0, i] = (acc_ref[i] * alpha + p * nv[i : i + 1]) / denom


def paged_mqa_decode_pallas(
    q: jnp.ndarray,  # [B, Hkv, G, D]
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, Dk]  int8 payload or bf16
    v_pool: jnp.ndarray,
    k_scale,  # [L, P, Hkv, ps] f32, or None when kv_bits == 16
    v_scale,
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    lengths: jnp.ndarray,  # [B] int32 — tokens already in the cache
    layer: jnp.ndarray,  # [] or [1] int32 — which pool layer to read
    new_k: jnp.ndarray,  # [B, Hkv, Dk] — this step's K/V, not yet in the pool
    new_v: jnp.ndarray,
    new_k_scale,  # [B, Hkv] f32, or None
    new_v_scale,
    *,
    kv_bits: int,
    sm_scale: float,
    window: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hkv, g, d = q.shape
    ps, dk = k_pool.shape[3], k_pool.shape[4]
    n_w = tables.shape[1]
    n_pl = d // dk
    quant = kv_bits < 16
    lengths = lengths.astype(jnp.int32)
    # per-row first in-window position over the total (cache + new) length;
    # window may be a traced scalar (per-layer windows come out of lax.scan)
    if window is not None:
        win_lo = jnp.maximum(lengths + 1 - jnp.asarray(window, jnp.int32), 0)
    else:
        win_lo = jnp.zeros_like(lengths)
    qp = to_planes(q.astype(jnp.float32), kv_bits, axis=2)  # [B, Hkv, n_pl, G, Dk]
    nk = to_planes(dequant_kv(new_k, new_k_scale, kv_bits), kv_bits, axis=2)
    nv = to_planes(dequant_kv(new_v, new_v_scale, kv_bits), kv_bits, axis=2)

    def page_map(b_, h_, w_, tables_ref, lengths_ref, win_lo_ref, layer_ref):
        # Clamp dead slots to the row's nearest live page — below the window
        # start as well as past the length: consecutive grid steps then index
        # the same block and Pallas skips the re-fetch, so windowed layers
        # DMA ~window/ps pages per token, not the whole cache.
        n_live = (lengths_ref[b_] + ps - 1) // ps
        first = win_lo_ref[b_] // ps  # 0 when no window
        slot = jnp.clip(jnp.maximum(w_, first), 0, jnp.maximum(n_live - 1, 0))
        return (layer_ref[0], tables_ref[b_, slot], h_, 0, 0)

    def scale_map(b_, h_, w_, *refs):
        page = page_map(b_, h_, w_, *refs)
        return (page[0], page[1], 0, 0)

    def head_map(b_, h_, w_, *_):
        return (b_, h_, 0, 0, 0)

    def tok_map(b_, h_, w_, *_):
        return (b_, h_, 0, 0)

    page_spec = pl.BlockSpec((1, 1, 1, ps, dk), page_map)
    in_specs = [pl.BlockSpec((1, 1, n_pl, g, dk), head_map), page_spec, page_spec]
    inputs = [qp, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, hkv, ps), scale_map)] * 2
        inputs += [k_scale, v_scale]
    in_specs += [pl.BlockSpec((1, 1, n_pl, dk), tok_map)] * 2
    inputs += [nk, nv]

    kernel = functools.partial(
        _paged_kernel,
        ps=ps,
        kv_bits=kv_bits,
        sm_scale=sm_scale,
        n_w=n_w,
        has_window=window is not None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv, n_w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, n_pl, g, dk), head_map),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((n_pl, g, dk), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_pl, g, dk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=f"paged_mqa_decode_kv{kv_bits}",
    )(
        tables.astype(jnp.int32),
        lengths,
        win_lo,
        jnp.asarray(layer, jnp.int32).reshape(1),
        *inputs,
    )
    return from_planes(out, axis=2).astype(q.dtype)


def paged_mqa_decode_xla(
    q: jnp.ndarray,  # [B, Hkv, G, D]
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, Dk]
    v_pool: jnp.ndarray,
    k_scale,  # [L, P, Hkv, ps] f32, or None
    v_scale,
    tables: jnp.ndarray,  # [B, W] int32
    lengths: jnp.ndarray,  # [B] int32
    layer,  # scalar int32
    new_k: jnp.ndarray,  # [B, Hkv, Dk]
    new_v: jnp.ndarray,
    new_k_scale,  # [B, Hkv] f32, or None
    new_v_scale,
    *,
    kv_bits: int,
    sm_scale: float,
    window: int | None = None,
) -> jnp.ndarray:
    """XLA fallback: lax.scan over page slots, one [B]-page gather per live
    slot through the table.  ``lax.cond`` skips slots past the longest row,
    so CPU walltime scales with occupied length, not table capacity (the
    kernel's per-row clamping, batch-coarsened)."""
    b, hkv, g, d = q.shape
    n_layers, n_pages, _, ps = k_pool.shape[:4]
    n_w = tables.shape[1]
    quant = kv_bits < 16
    layer = jnp.asarray(layer, jnp.int32).reshape(())

    # fold the layer index into the page axis so per-slot gathers never
    # materialize a whole layer's pool slice
    kp = k_pool.reshape(n_layers * n_pages, hkv, ps, -1)
    vp = v_pool.reshape(n_layers * n_pages, hkv, ps, -1)
    if quant:
        ksp = k_scale.reshape(n_layers * n_pages, hkv, ps)
        vsp = v_scale.reshape(n_layers * n_pages, hkv, ps)
    base = layer * n_pages
    lengths = lengths.astype(jnp.int32)
    qf = q.astype(jnp.float32)
    lo = lengths + 1 - window if window is not None else None

    def slot_step(carry, w):
        def update(carry):
            m, l, acc = carry
            pages = base + tables[:, w]  # [B]
            # [B, Hkv, ps, D] f32
            kf = dequant_kv(kp[pages], ksp[pages] if quant else None, kv_bits)
            vf = dequant_kv(vp[pages], vsp[pages] if quant else None, kv_bits)
            scores = jnp.einsum("bhgd,bhsd->bhgs", qf, kf) * sm_scale
            pos = w * ps + jnp.arange(ps, dtype=jnp.int32)[None, :]  # [1, ps]
            valid = pos < lengths[:, None]
            if window is not None:
                valid = valid & (pos >= lo[:, None])
            vmask = valid[:, None, None, :]
            scores = jnp.where(vmask, scores, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(vmask, jnp.exp(scores - m_new), 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhgs,bhsd->bhgd", p, vf)
            return m_new, l_new, acc_new

        # a slot is live if ANY row has cached tokens in it that fall inside
        # its window — per-row, so short or pow2-padding rows (lengths == 0)
        # can't pin the whole batch's scan open
        alive = w * ps < lengths
        if window is not None:
            alive = alive & ((w + 1) * ps > jnp.maximum(lo, 0))
        carry = jax.lax.cond(jnp.any(alive), update, lambda c: c, carry)
        return carry, None

    m0 = jnp.full((b, hkv, g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        slot_step, (m0, l0, a0), jnp.arange(n_w, dtype=jnp.int32)
    )

    # fused new-token term (always valid, never read from the pool)
    nkf = dequant_kv(new_k, new_k_scale, kv_bits)  # [B, Hkv, D]
    nvf = dequant_kv(new_v, new_v_scale, kv_bits)
    s_new = jnp.einsum("bhgd,bhd->bhg", qf, nkf)[..., None] * sm_scale
    m_new = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s_new - m_new)
    denom = l * alpha + p
    acc = acc * alpha + p * nvf[:, :, None, :]
    return (acc / jnp.maximum(denom, 1e-20)).astype(q.dtype)
