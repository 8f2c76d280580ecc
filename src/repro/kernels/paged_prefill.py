"""Paged chunk-prefill GQA attention: a query *chunk* against the KV pool.

Chunked prefill processes a prompt's uncached suffix ``C`` tokens at a time:
chunk queries (positions ``ctx .. ctx + C``) attend to (a) every token
already materialized in the paged pool — the prefix-cache hit plus earlier
chunks — and (b) the chunk itself, causally.  This is the prefill analogue of
``kernels/paged_decode.py`` (a decode step is a chunk of one):

* grid ``(batch, kv_head, page_slot)`` with the slot dimension innermost and
  sequential; the same scalar-prefetched page-table index map translates
  ``(row, slot) -> page_id`` and clamps dead slots (at/past ``ctx_lens[b]``,
  or wholly below the sliding-window start) to the row's nearest live page so
  they cost neither DMA nor compute — chunk attention traffic scales with the
  tokens actually cached, not table capacity.
* pool pages are the decode kernel's head-major ``[ps, Dk]`` tiles; int8/int4
  payloads dequantize in-register with per-(token, head) scale rows and
  (even, odd) head-dim planes, exactly like the decode kernel.  Queries
  arrive flattened to ``[C*G, Dk]`` rows (row ``r`` is chunk token
  ``r // G``) so every block is a whole 2-D tile.
* the chunk's own K/V (computed this step, not yet in the pool, dequantized
  to f32 by the wrapper) enters the online softmax in the final grid step
  under a causal-within-chunk mask (key j visible to query c iff
  ``j <= c``), with per-row valid lengths
  ``q_lens`` masking bucket padding; the caller scatters the chunk into its
  pages afterwards.  Cached positions are all ``< ctx`` so causality against
  the pool is automatic; sliding windows mask per (query, key) distance.

``paged_mqa_prefill_xla`` is the CPU/interpret fallback: a ``lax.scan`` over
page slots with ``lax.cond`` slot skipping, then one fused self-chunk update.
Oracle: ``kernels/ref.py::paged_mqa_prefill_ref``; dispatch:
``kernels/ops.py::paged_mqa_prefill``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_decode import (
    dequant_kv,
    dot_nn,
    dot_nt,
    from_planes,
    payload_planes,
    to_planes,
)

__all__ = ["paged_mqa_prefill_pallas", "paged_mqa_prefill_xla"]

_NEG_INF = -1e30


def _prefill_kernel(
    # scalar prefetch
    tables_ref,  # [B, W] int32
    ctx_ref,  # [B] int32 — tokens already in the pool
    qlen_ref,  # [B] int32 — valid chunk tokens (<= C; rest is padding)
    win_lo_ref,  # [B] int32 — first in-window pool position (0 if no window)
    win_ref,  # [1] int32 — window size (may be traced; 0 when has_window=False)
    layer_ref,  # [1] int32
    # blocks
    q_ref,  # [1, 1, n_pl, C*G, Dk] f32
    k_ref,  # [1, 1, 1, ps, Dk]   (one page of one kv head)
    v_ref,
    *rest,  # [ks_ref, vs_ref,] ck_ref, cv_ref, o_ref + scratch
    ps: int,
    kv_bits: int,
    sm_scale: float,
    n_w: int,
    c: int,
    g: int,
    has_window: bool,
):
    quant = kv_bits < 16
    if quant:
        ks_ref, vs_ref, ck_ref, cv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ck_ref, cv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    n_pl = q_ref.shape[2]

    b_idx = pl.program_id(0)
    h_idx = pl.program_id(1)
    w_idx = pl.program_id(2)
    ctx = ctx_ref[b_idx]

    @pl.when(w_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = [q_ref[0, 0, i] for i in range(n_pl)]  # n_pl x [C*G, Dk]
    # chunk index of each flattened query row, and its absolute position
    c_of_r = jax.lax.broadcasted_iota(jnp.int32, (c * g, 1), 0) // g  # [C*G, 1]
    q_pos = ctx + c_of_r

    block_live = w_idx * ps < ctx
    if has_window:
        block_live = block_live & ((w_idx + 1) * ps > win_lo_ref[b_idx])

    def online_update(scores, valid, vp, p_scale=None):
        """One online-softmax update: scores/valid [C*G, S], vp planes
        [S, Dk]; ``p_scale`` is the [1, S] V-scale row (quantized pages)."""
        scores = jnp.where(valid, scores * sm_scale, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if p_scale is not None:
            p = p * p_scale
        for i in range(n_pl):
            acc_ref[i] = acc_ref[i] * alpha + dot_nn(p, vp[i])
        m_ref[...] = m_new

    def qk(kp):
        scores = dot_nt(q[0], kp[0])
        for i in range(1, n_pl):
            scores = scores + dot_nt(q[i], kp[i])
        return scores

    @pl.when(block_live)
    def _pool_update():
        scores = qk(payload_planes(k_ref[0, 0, 0], kv_bits))  # [C*G, ps]
        vs = None
        if quant:
            scores = scores * ks_ref[0, 0, pl.ds(h_idx, 1), :]
            vs = vs_ref[0, 0, pl.ds(h_idx, 1), :]
        pos = w_idx * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        valid = pos < ctx  # pool tokens all precede the chunk: causal for free
        if has_window:
            valid = valid & (q_pos - pos < win_ref[0])
        online_update(scores, valid, payload_planes(v_ref[0, 0, 0], kv_bits), vs)

    @pl.when(w_idx == n_w - 1)
    def _self_chunk():
        # the chunk attends to itself causally (key j visible iff j <= c);
        # padding rows (c >= q_len) mask every key and normalize to zero.
        ck = [ck_ref[0, 0, i] for i in range(n_pl)]  # [C, Dk] f32, dequantized
        cv = [cv_ref[0, 0, i] for i in range(n_pl)]
        scores = qk(ck)  # [C*G, C]
        j = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
        valid = (j <= c_of_r) & (j < qlen_ref[b_idx])
        if has_window:
            valid = valid & (c_of_r - j < win_ref[0])
        online_update(scores, valid, cv)
        denom = jnp.maximum(l_ref[...], 1e-20)
        for i in range(n_pl):
            o_ref[0, 0, i] = acc_ref[i] / denom


def paged_mqa_prefill_pallas(
    q: jnp.ndarray,  # [B, Hkv, C, G, D]
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, Dk]  int8 payload or bf16
    v_pool: jnp.ndarray,
    k_scale,  # [L, P, Hkv, ps] f32, or None when kv_bits == 16
    v_scale,
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    ctx_lens: jnp.ndarray,  # [B] int32 — tokens already in the pool
    q_lens: jnp.ndarray,  # [B] int32 — valid chunk tokens per row
    layer: jnp.ndarray,  # [] or [1] int32 — which pool layer to read
    chunk_k: jnp.ndarray,  # [B, Hkv, C, Dk] — this chunk's K/V, not yet pooled
    chunk_v: jnp.ndarray,
    chunk_k_scale,  # [B, Hkv, C] f32, or None
    chunk_v_scale,
    *,
    kv_bits: int,
    sm_scale: float,
    window=None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hkv, c, g, d = q.shape
    ps, dk = k_pool.shape[3], k_pool.shape[4]
    n_w = tables.shape[1]
    n_pl = d // dk
    quant = kv_bits < 16
    ctx_lens = ctx_lens.astype(jnp.int32)
    if window is not None:
        win_lo = jnp.maximum(ctx_lens + 1 - jnp.asarray(window, jnp.int32), 0)
    else:
        win_lo = jnp.zeros_like(ctx_lens)
    qp = to_planes(
        q.reshape(b, hkv, c * g, d).astype(jnp.float32), kv_bits, axis=2
    )  # [B, Hkv, n_pl, C*G, Dk]
    ck = to_planes(dequant_kv(chunk_k, chunk_k_scale, kv_bits), kv_bits, axis=2)
    cv = to_planes(dequant_kv(chunk_v, chunk_v_scale, kv_bits), kv_bits, axis=2)

    def page_map(b_, h_, w_, tables_ref, ctx_ref, qlen_ref, win_lo_ref, win_ref, layer_ref):
        n_live = (ctx_ref[b_] + ps - 1) // ps
        first = win_lo_ref[b_] // ps  # 0 when no window
        slot = jnp.clip(jnp.maximum(w_, first), 0, jnp.maximum(n_live - 1, 0))
        return (layer_ref[0], tables_ref[b_, slot], h_, 0, 0)

    def scale_map(b_, h_, w_, *refs):
        page = page_map(b_, h_, w_, *refs)
        return (page[0], page[1], 0, 0)

    def head_map(b_, h_, w_, *_):
        return (b_, h_, 0, 0, 0)

    page_spec = pl.BlockSpec((1, 1, 1, ps, dk), page_map)
    in_specs = [pl.BlockSpec((1, 1, n_pl, c * g, dk), head_map), page_spec, page_spec]
    inputs = [qp, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, hkv, ps), scale_map)] * 2
        inputs += [k_scale, v_scale]
    in_specs += [pl.BlockSpec((1, 1, n_pl, c, dk), head_map)] * 2
    inputs += [ck, cv]

    kernel = functools.partial(
        _prefill_kernel,
        ps=ps,
        kv_bits=kv_bits,
        sm_scale=sm_scale,
        n_w=n_w,
        c=c,
        g=g,
        has_window=window is not None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b, hkv, n_w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, n_pl, c * g, dk), head_map),
        scratch_shapes=[
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((n_pl, c * g, dk), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_pl, c * g, dk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=f"paged_mqa_prefill_kv{kv_bits}",
    )(
        tables.astype(jnp.int32),
        ctx_lens,
        q_lens.astype(jnp.int32),
        win_lo,
        jnp.asarray(0 if window is None else window, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *inputs,
    )
    return from_planes(out, axis=2).reshape(b, hkv, c, g, d).astype(q.dtype)


def paged_mqa_prefill_xla(
    q: jnp.ndarray,  # [B, Hkv, C, G, D]
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, Dk]
    v_pool: jnp.ndarray,
    k_scale,  # [L, P, Hkv, ps] f32, or None
    v_scale,
    tables: jnp.ndarray,  # [B, W] int32
    ctx_lens: jnp.ndarray,  # [B] int32
    q_lens: jnp.ndarray,  # [B] int32
    layer,  # scalar int32
    chunk_k: jnp.ndarray,  # [B, Hkv, C, Dk]
    chunk_v: jnp.ndarray,
    chunk_k_scale,  # [B, Hkv, C] f32, or None
    chunk_v_scale,
    *,
    kv_bits: int,
    sm_scale: float,
    window=None,
) -> jnp.ndarray:
    """XLA fallback: lax.scan over page slots (lax.cond skips slots past the
    longest row), then one fused causal self-chunk softmax update."""
    b, hkv, c, g, d = q.shape
    n_layers, n_pages, _, ps = k_pool.shape[:4]
    n_w = tables.shape[1]
    quant = kv_bits < 16
    layer = jnp.asarray(layer, jnp.int32).reshape(())

    kp = k_pool.reshape(n_layers * n_pages, hkv, ps, -1)
    vp = v_pool.reshape(n_layers * n_pages, hkv, ps, -1)
    if quant:
        ksp = k_scale.reshape(n_layers * n_pages, hkv, ps)
        vsp = v_scale.reshape(n_layers * n_pages, hkv, ps)
    base = layer * n_pages
    ctx_lens = ctx_lens.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    qf = q.astype(jnp.float32)
    cpos = jnp.arange(c, dtype=jnp.int32)
    q_pos = ctx_lens[:, None] + cpos[None, :]  # [B, C] absolute query positions
    lo = q_pos + 1 - window if window is not None else None

    def update(carry, kf, vf, valid):
        """kf/vf [B, Hkv, S, D]; valid [B, C, S]."""
        m, l, acc = carry
        scores = jnp.einsum("bhcgd,bhsd->bhcgs", qf, kf) * sm_scale
        vmask = valid[:, None, :, None, :]  # [B, 1, C, 1, S]
        scores = jnp.where(vmask, scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(vmask, jnp.exp(scores - m_new), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("bhcgs,bhsd->bhcgd", p, vf)
        return m_new, l_new, acc_new

    def slot_step(carry, w):
        def live(carry):
            pages = base + tables[:, w]  # [B]
            kf = dequant_kv(kp[pages], ksp[pages] if quant else None, kv_bits)
            vf = dequant_kv(vp[pages], vsp[pages] if quant else None, kv_bits)
            pos = w * ps + jnp.arange(ps, dtype=jnp.int32)[None, None, :]  # [1,1,ps]
            valid = pos < ctx_lens[:, None, None]  # [B, 1, ps] -> broadcast C
            valid = jnp.broadcast_to(valid, (b, c, ps))
            if window is not None:
                valid = valid & (pos >= lo[:, :, None])
            return update(carry, kf, vf, valid)

        alive = w * ps < ctx_lens
        if window is not None:
            alive = alive & ((w + 1) * ps > jnp.maximum(ctx_lens + 1 - window, 0))
        return jax.lax.cond(jnp.any(alive), live, lambda cr: cr, carry), None

    m0 = jnp.full((b, hkv, c, g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, c, g, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, c, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        slot_step, (m0, l0, a0), jnp.arange(n_w, dtype=jnp.int32)
    )

    # fused causal self-chunk term (keys are the chunk's own not-yet-pooled
    # K/V at positions ctx + j)
    ckf = dequant_kv(chunk_k, chunk_k_scale, kv_bits)  # [B, Hkv, C, D]
    cvf = dequant_kv(chunk_v, chunk_v_scale, kv_bits)
    j = cpos[None, None, :]  # [1, 1, C] key chunk index
    valid = (j <= cpos[None, :, None]) & (j < q_lens[:, None, None])  # [B, C, C]
    if window is not None:
        valid = valid & (cpos[None, :, None] - j < window)
    m, l, acc = update((m, l, acc), ckf, cvf, valid)
    out = acc / jnp.maximum(l, 1e-20)
    return out.astype(q.dtype)
